"""The open-loop generator: the same seed gives the same run, every seed
gives the same work in another order, lengths stay inside the clip."""
import numpy as np

from benchmarks.traffic import open_loop

MIX = {"rate_rps": 2.5,
       "prompt_tokens": {"median": 64, "sigma": 0.6, "min": 16, "max": 256},
       "output_tokens": {"median": 160, "sigma": 0.5, "min": 64, "max": 384},
       "ramp_s": 5.0, "drain_s": 15.0, "schedule_seed": 25}
BIG = 2 ** 31 + 11            # the driver's seeds do not fit 32 signed bits


def _key(reqs):
    return [(r.due_s, r.prompt.tolist(), r.max_new, r.measured) for r in reqs]


def test_same_seed_same_requests():
    a = open_loop.generate(MIX, 30.0, BIG, 50304)
    b = open_loop.generate(MIX, 30.0, BIG, 50304)
    assert _key(a) == _key(b)


def test_every_seed_offers_the_same_work_in_another_order():
    runs = [open_loop.generate(MIX, 30.0, s, 50304) for s in (0, 1, BIG)]
    sets = [sorted((len(r.prompt), r.max_new) for r in run if r.measured)
            for run in runs]
    assert sets[0] == sets[1] == sets[2]
    assert len(sets[0]) == 75                       # rate x seconds, exactly
    orders = [[len(r.prompt) for r in run if r.measured] for run in runs]
    assert orders[0] != orders[1]
    tokens = [run[-1].prompt.tolist() for run in runs]
    assert tokens[0] != tokens[1]


def test_clipping_and_window():
    reqs = open_loop.generate(MIX, 30.0, 7, 50304)
    for r in reqs:
        assert 16 <= len(r.prompt) <= 256 and 64 <= r.max_new <= 384
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 50304
        assert r.measured == (0.0 <= r.due_s < 30.0)
        assert r.due_s >= -5.0
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
    ramp = [r for r in reqs if not r.measured]
    assert ramp, "the ramp replays the end of the period before the window"


def test_ramp_longer_than_the_window():
    reqs = open_loop.generate(dict(MIX, ramp_s=5.0), 2.0, 3, 1000)
    assert sum(r.measured for r in reqs) == 5
    assert min(r.due_s for r in reqs) >= -5.0
    assert sum(not r.measured for r in reqs) >= 10    # two whole laps and a bit
