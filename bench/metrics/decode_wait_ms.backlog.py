from bench.steplog import decode_wait_ms as read  # noqa: F401
