from bench.steplog import engine_tick_ms as read  # noqa: F401
