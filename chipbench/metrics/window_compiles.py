"""JAX's backend-compile events (compiles and persistent-cache loads) inside
the measured window. Every shape is warmed up in set-up, so this should
read 0."""


def read(trace, record):
    return record["run"]["window"]["compiles"]
