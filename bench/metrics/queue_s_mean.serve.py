"""Mean time from a request's due time to the engine step that admitted
it into a lane, on the harness's clock, over the requests due in the
window."""


def read(rec):
    return rec.get("queue_s_mean")
