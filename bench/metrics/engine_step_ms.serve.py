"""Mean of the engine's own ``serve.step_seconds`` over the window: host
clock around each engine step, after it blocks on the device."""


def read(rec):
    v = rec.get("engine_step_s_mean")
    return None if v is None else 1e3 * v
