"""The executable's compile seconds in the cold invocation
(``CompiledEngine.compile_s`` with both compile caches off)."""


def read(r):
    return r.xla_compile_s
