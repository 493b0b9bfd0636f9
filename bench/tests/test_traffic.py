"""The serve mix offers every seed the same work in another order, and its
three stretches of arrivals follow one another without a gap."""
from conftest import tiny


def _sizes(reqs):
    return sorted((len(t.req.prompt), t.req.max_new) for t in reqs)


def test_every_seed_gets_the_same_window_work():
    cell = tiny("qwen3-1.7b.serve-chat")
    mod = cell.runner()
    tr, vocab = cell.traffic, cell.config["vocab_size"]
    a = mod.schedule(tr, 2 ** 33 + 1, 10.0, vocab)
    b = mod.schedule(tr, 5, 10.0, vocab)
    for sa, sb in zip(a, b):
        assert _sizes(sa) == _sizes(sb)
        assert [t.req.prompt for t in sa] != [t.req.prompt for t in sb]


def test_stretches_cover_ramp_window_and_drain():
    cell = tiny("qwen3-1.7b.serve-chat")
    mod = cell.runner()
    tr = dict(cell.traffic, ramp_s=3.0, drain_s=5.0)
    ramp, window, after = mod.schedule(tr, 7, 10.0,
                                       cell.config["vocab_size"])
    assert all(0.0 <= t.due < 3.0 for t in ramp)
    assert all(3.0 <= t.due < 13.0 for t in window)
    assert all(13.0 <= t.due < 18.0 for t in after)
    assert len(window) == round(tr["rate_per_s"] * 10.0)
    rids = [t.req.rid for t in ramp + window + after]
    assert len(set(rids)) == len(rids)
