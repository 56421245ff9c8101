"""Set-up seconds spent building programs: XLA compiles and loads from
the persistent compilation cache (JAX monitoring events)."""


def read(data):
    return data["setup_compile_s"]
