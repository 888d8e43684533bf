"""The correctness check's control at a tiny size on the CPU: the plain
reference computed with every matmul in float8 (the precision below the
bfloat16 the configurations state), put in the program's place, fails
the benchmark's own comparison (``chipbench.check``, every limit the
configuration names) that the sound program passes."""

import gc

import pytest

import benchtiny as tiny
from chipbench import cell as C
from chipbench import check as K
from chipbench import reference as R


@pytest.mark.parametrize("kind", ["moe", "dense"])
def test_float8_control_fails_where_the_program_passes(kind):
    cs = tiny.cell(kind)
    conf, mix = cs["config"], cs["traffic"]
    seed = 2 ** 31 + 101
    eng, sc = C.build(conf, seed)
    C.warm_up(eng, sc, conf, mix, 3.0)
    rec = C.run_window(eng, sc, conf, mix, seed, 3.0)
    ids = C.sample_finished(eng, rec, seed, mix["ref_requests"])
    seqs = [(eng.prompts[i].tolist(), list(eng.outputs[i])) for i in ids]
    del eng
    gc.collect()
    g = R.gaps(conf, seed, seqs, sc.max_len, control=True)
    served = K.checks(conf, R.summary(g["served"]))
    control = K.checks(conf, R.summary(g["control"]))
    assert set(served) == {"mean_logit_gap", "widest_logit_gap"}
    assert K.passed(served), served
    assert not K.passed(control), control
    # each limit alone separates the two: the control reads three times
    # the limit or more
    for name, c in control.items():
        assert c["value"] > 3 * c["limit"], (name, control)
