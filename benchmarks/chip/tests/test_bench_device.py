"""The command refuses to measure anywhere but on the chips it names:
on the CPU it exits non-zero and prints no result, and a TPU whose kind
``peaks.json`` does not list is refused too."""

import types

import pytest

import benchtiny  # noqa: F401  (puts the benchmark and the program on the path)
import bench
from chipbench import cell


def test_cpu_device_fails_without_a_result(capsys):
    rc = bench.main(["--workload", "qwen3-a3b.sharegpt-poisson",
                     "--seed", str(2 ** 31 + 3), "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "not a TPU" in out.err


def _fake(monkeypatch, platform, kind, n=1):
    import jax
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * n)


def test_unknown_device_kind_fails(monkeypatch):
    _fake(monkeypatch, "tpu", "TPU v9 imaginary")
    with pytest.raises(cell.NoChip, match="not in peaks.json"):
        cell.check_device(1)


def test_too_few_chips_fail(monkeypatch):
    _fake(monkeypatch, "tpu", "TPU v5 lite", n=1)
    with pytest.raises(cell.NoChip, match="needs 4"):
        cell.check_device(4)
    assert cell.check_device(1) == {"platform": "tpu",
                                    "kind": "TPU v5 lite", "count": 1}
