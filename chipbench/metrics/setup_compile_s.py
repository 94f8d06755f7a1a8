"""Seconds of backend compiles during set-up (a persistent-cache hit
compiles nothing and adds nothing)."""


def read(record):
    return record["setup_compile_s"]
