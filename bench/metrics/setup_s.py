"""setup_s: process start to the first timed request: JAX and chip
bring-up, scenario build, one warm request (which compiles the device
programs, or loads them from the persistent cache)."""


def read(rec):
    return rec["setup_s"]
