"""chip_smoke.py at smoke size on the CPU: its phases (both schedulers over
one copy of the weights, then the HTTP/SSE server) run end to end on the
tiny same-family model, and ``main`` refuses to run without a TPU."""

from __future__ import annotations

import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

from repro.configs import get_smoke_config  # noqa: E402
from repro.launch.config import ServeConfig  # noqa: E402
from repro.launch.serve import model_and_params  # noqa: E402


def _smoke_config():
    return ServeConfig(arch=chip_smoke.ARCH, smoke=True, slots=4,
                       max_len=96, requests=4).validate()


def test_main_exits_nonzero_without_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    assert '"ok": true' not in capsys.readouterr().out


def test_phases_at_smoke_size(capsys):
    sc = _smoke_config()
    vocab = get_smoke_config(chip_smoke.ARCH).vocab_size
    requests = chip_smoke.make_requests(vocab, 4, prompt_len=(24, 40),
                                        new_tokens=(4, 8))
    res = chip_smoke.run(sc, requests, n_http=2)
    out = capsys.readouterr().out
    for s in ("layered", "chunked"):
        r = res["runs"][s]
        assert [len(o) for o in r["outputs"]] == [n for _, n in requests]
        assert r["n_prefill_compiles"] >= 1
    assert [len(t) for t in res["http"]] == [n for _, n in requests[:2]]
    # greedy float32 streams are schedule-invariant on the CPU
    assert res["runs"]["layered"]["outputs"] == \
        res["runs"]["chunked"]["outputs"]
    assert res["http"] == res["runs"]["layered"]["outputs"][:2]
    assert "4/4 streams identical" in out and "2/2 streams" in out
    # in float32 every served token is the full forward's argmax
    for s in ("layered", "chunked"):
        assert res["ranks"][s] == [[0] * n for _, n in requests]


def test_reference_ranks_catch_a_wrong_token():
    sc = _smoke_config()
    model, params = model_and_params(sc)
    from repro.launch.serve import build_engine
    eng = build_engine(sc)
    reqs = chip_smoke.make_requests(model.cfg.vocab_size, 2,
                                    prompt_len=(8, 12), new_tokens=(3, 3))
    rids = [eng.submit(p, max_new_tokens=n) for p, n in reqs]
    eng.run()
    outs = [list(eng.outputs[r]) for r in rids]
    assert chip_smoke.reference_ranks(model, params, reqs, outs) == \
        [[0, 0, 0], [0, 0, 0]]
    # any other last token is not the reference's argmax
    worst = [o[:-1] + [(o[-1] + 1) % model.cfg.vocab_size] for o in outs]
    ranks = chip_smoke.reference_ranks(model, params, reqs, worst)
    assert all(r[:2] == [0, 0] and r[2] > 0 for r in ranks)
    # tokens served from other weights fail the share gate
    import jax
    other = jax.jit(model.init)(jax.random.PRNGKey(1))
    flat = sum(chip_smoke.reference_ranks(model, other, reqs, outs), [])
    assert flat.count(0) / len(flat) < chip_smoke.REF_SHARE


def test_engines_share_one_copy_of_the_weights():
    sc = _smoke_config()
    m1, p1 = model_and_params(sc)
    m2, p2 = model_and_params(dataclasses.replace(sc, scheduler="chunked"))
    assert m1 is m2 and p1 is p2


def test_served_check_rejects_short_stream():
    from repro.launch.serve import build_engine
    sc = _smoke_config()
    eng = build_engine(sc)
    vocab = eng.cfg.vocab_size
    reqs = chip_smoke.make_requests(vocab, 2, prompt_len=(8, 12),
                                    new_tokens=(3, 3))
    rids = [eng.submit(p, max_new_tokens=n) for p, n in reqs]
    eng.run()
    chip_smoke._check_served(eng, rids, reqs)
    with pytest.raises(RuntimeError, match="tokens, wanted"):
        chip_smoke._check_served(eng, rids, [(p, n + 1) for p, n in reqs])


def test_full_width_config_is_built_not_substituted(monkeypatch):
    """Without --smoke the named configuration is the one sized: on a
    device too small for it the weights helper fails with the byte counts
    instead of serving a toy."""
    import jax
    from repro.launch import serve

    class Small:
        device_kind = "tiny chip"

        def memory_stats(self):
            return {"bytes_limit": 10**9}

    monkeypatch.setattr(jax, "devices", lambda *a: [Small()])
    sc = ServeConfig(arch=chip_smoke.ARCH, slots=8, max_len=2048).validate()
    with pytest.raises(MemoryError, match=r"need \d+ bytes.*holds 1000000000"):
        serve.model_and_params(sc)


def test_compile_cache_dir_from_env_or_fixed_path(monkeypatch):
    import jax
    from repro.launch import serve
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert serve.setup_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = serve.setup_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
