"""The seeded weights and the plain reference, on the CPU at a tiny size:
the weight layout is the program's, a layer rebuilt alone equals the same
layer of the stack bit for bit, and in float32 the reference agrees with
the program's own full forward pass on every greedy token."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtiny as tiny
from chipbench import cell
from chipbench import reference as R
from chipbench import weights as W


@pytest.mark.parametrize("kind", ["moe", "dense"])
def test_layout_is_the_programs(kind):
    from repro.models.model import DecoderModel
    conf = tiny.config(kind)
    model = DecoderModel(cell.program_config(conf))
    got = W.tree_layout(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    assert got == {k: s for k, (s, _) in W.layout(conf).items()}


def test_a_layer_rebuilt_alone_equals_the_stack():
    conf = tiny.config("moe")
    lay = W.layout(conf)
    seed = 2 ** 32 + 11
    params = W.make(lay, seed, jnp.bfloat16)
    path = W.LAYER + "moe/w_up"
    shape = lay[path][0]
    alone = W.leaf(W.seed_key(seed), path, shape[1:], 1, jnp.bfloat16)
    stacked = params["segments"][0]["pattern"][0]["moe"]["w_up"][1]
    assert np.array_equal(np.asarray(alone, np.float32),
                          np.asarray(stacked, np.float32))
    other = W.make(lay, seed + 1, jnp.bfloat16)
    assert not np.array_equal(
        np.asarray(other["embed"]["tok"], np.float32),
        np.asarray(params["embed"]["tok"], np.float32))


@pytest.mark.parametrize("kind", ["moe", "dense"])
def test_reference_agrees_with_the_program_in_float32(kind):
    from repro.models.model import DecoderModel
    conf = tiny.config(kind, dtype="float32")
    model = DecoderModel(cell.program_config(conf))
    seed = 5
    params = W.make(W.layout(conf), seed, jnp.float32)
    fwd = jax.jit(lambda p, t: model.forward(p, t, dropless=True)[0])
    rng = np.random.default_rng(0)
    seqs = []
    for n in (20, 37):
        prompt = rng.integers(1, 512, n).tolist()
        toks = list(prompt)
        for _ in range(6):                       # greedy, full forward
            logits = fwd(params, jnp.asarray([toks], jnp.int32))
            toks.append(int(jnp.argmax(logits[0, -1])))
        seqs.append((prompt, toks[n:]))
    g = R.gaps(conf, seed, seqs, 128)["served"]
    assert R.summary(g)["widest"] < 1e-4
    wrong = [(p, [(s[0] + 1) % 512] + s[1:]) for p, s in seqs]
    assert min(x[0] for x in R.gaps(conf, seed, wrong, 128)["served"]) > 0
