"""Backend compiles (`/jax/core/compile/backend_compile_duration`
events) inside the measured window: there should be none."""


def read(record):
    return record["window_compiles"]
