"""The warm-up serves the merged cohorts that the window's schedule
forms, found by replaying the schedule through the cell's scheduler on
the host."""

import benchtiny as tiny
from chipbench import cell as C
from chipbench import traffic as T


def _cell():
    cs = tiny.cell("moe")
    conf, mix = cs["config"], cs["traffic"]
    # arrivals closer together than a cohort's prefill takes, so that
    # requests queue behind a cohort and merge into the next
    return conf, dict(mix, rate_rps=20.0)


def test_replay_finds_the_requests_admitted_together():
    conf, mix = _cell()
    eng, sc = C.build(conf, 5)
    together = C.cohorts(eng, sc, conf, mix, 3.0)
    assert together and all(len(c) >= 2 for c in together)
    assert len(set(together)) == len(together)
    lengths = {len(r.prompt) for r in T.open_loop(mix, 3.0, 0, sc.max_len,
                                                   eng.cfg.vocab_size)}
    assert all(set(c) <= lengths for c in together)


def test_warm_up_serves_each_cohort_at_once():
    conf, mix = _cell()
    eng, sc = C.build(conf, 5)
    w = C.warm_up(eng, sc, conf, mix, 3.0)
    assert w["cohorts"] == C.cohorts(eng, sc, conf, mix, 3.0)
    # a cohort of several requests runs its prefill on a wider row
    # bucket than any lone prompt
    rows = {key[3] for key in eng._jit_prefill if len(key) == 5}
    assert 1 in rows and max(rows) >= 2, rows
