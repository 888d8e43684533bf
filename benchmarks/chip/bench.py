#!/usr/bin/env python3
"""On-chip serving benchmark: runs one cell of ``BENCHMARK.json`` once.

    python benchmarks/chip/bench.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

From the checkout's root, on a machine with the TPU chips the cell asks
for.  A run:
  1. fails (exit 3, no result) unless JAX's devices are TPUs of a kind
     that ``peaks.json`` lists, as many as the cell needs;
  2. builds the cell's configuration with the program's own constructors
     (``DecoderModel``, ``make_scheduler``, ``Engine``) over weights made
     on the device from ``--seed`` (``chipbench.weights``);
  3. turns on the program's persistent compile cache
     (``setup_compile_cache``) and warms up every prefill and decode shape
     the cell's traffic can use, merged cohorts included;
  4. drives ``ServingRuntime.run`` over ``EngineExecutor(engine,
     wall=True)`` for ``--seconds`` with the cell's traffic from the seed,
     counting compiles inside the window (there should be none);
  5. with ``--trace 1``, records a profiler trace of the window and
     reduces it (``chipbench.trace``), and reports the per-layer metrics
     instead of the end-to-end ones;
  6. where fewer requests finished in the window than the check
     samples, serves on for up to ``LATE_S`` seconds past its close for
     their answers; frees the program and checks what was served against
     the float32 reference (``chipbench.reference``);
  7. prints one JSON object as the last line of its output: ``correct``,
     ``attempted``, ``failed``, ``metrics``, ``device`` (with
     ``--trace 1`` also ``breakdown``), and last ``checks``, each number
     the correctness check compared beside its limit.  The same numbers
     are the last lines of its standard error.
Every line it prints names the platform, the device kind and the count.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


# how long past the window's close a run waits for the answers it checks,
# when fewer than the mix's ref_requests finished inside the window
LATE_S = 60.0


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the benchmark's spans, not every call
    opts.host_tracer_level = 1
    return opts


def serve_and_check(cs: dict, seed: int, seconds: float, tracing: bool,
                    label: dict, log, compiles=None) -> dict:
    """One run of one cell; returns the result object."""
    import jax
    from chipbench import cell as C
    from chipbench import check as K
    from chipbench import reference as R
    from chipbench import spec as S
    from chipbench import trace as TR
    conf, mix = cs["config"], cs["traffic"]
    eng, sc = C.build(conf, seed)
    w = C.warm_up(eng, sc, conf, mix, seconds)
    log(f"built {conf['name']} ({sc.scheduler}, {sc.slots} slots x "
        f"{sc.max_len}); warm-up served {w['lone']} prompts alone in "
        f"{w['lone_s']:.1f} s and the cohorts {w['cohorts']} in "
        f"{w['cohorts_s']:.1f} s (replay {w['replay_s']:.1f} s); "
        f"compile events so far {compiles.n if compiles else None}")
    tmp = None
    if tracing:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tmp, profiler_options=_trace_options())
    opened = {}
    rec = C.run_window(eng, sc, conf, mix, seed, seconds, tracing,
                       on_open=lambda: opened.setdefault(
                           "t", time.monotonic()), compiles=compiles)
    rec["setup_s"] = opened["t"] - T_START
    rec["peak_flops"] = S.peaks(label["kind"])["bf16_flops"]
    if tracing:
        jax.profiler.stop_trace()
        t0 = time.monotonic()
        pb = next(Path(tmp).rglob("*.xplane.pb"))
        rec["trace"] = TR.reduce(TR.extract(str(pb)))
        log(f"trace {pb.stat().st_size} bytes reduced in "
            f"{time.monotonic() - t0:.1f} s")
        shutil.rmtree(tmp, ignore_errors=True)
    mem = C.memory_peak()
    log(f"window {seconds} s: {len(rec['steps'])} steps, "
        f"{len(rec['requests'])} requests; compiles inside the window "
        f"{rec['compiles']}; device memory peak {mem} bytes")
    names = cs["per_layer"] if tracing else cs["end_to_end"]
    metrics = C.metrics(names, rec)

    late = C.finish_late(eng, rec, mix["ref_requests"], LATE_S)
    if late:
        log(f"served {late:.1f} s past the window's close for answers")
    ids = C.sample_finished(eng, rec, seed, mix["ref_requests"])
    seqs = [(eng.prompts[i].tolist(), list(eng.outputs[i])) for i in ids]
    bad = C.served_tokens_ok(eng, rec)
    del eng
    gc.collect()
    log(f"program freed: {C.memory_in_use()} bytes in use on the device")
    t0 = time.monotonic()
    g = {}
    if seqs:
        g = R.summary(R.gaps(conf, seed, seqs, sc.max_len,
                             rows=mix["ref_requests"])["served"])
        log(f"reference over {len(seqs)} finished requests in "
            f"{time.monotonic() - t0:.1f} s: logit gaps {json.dumps(g)}")
    checks = K.checks(conf, g)
    due = [r for r in rec["requests"] if r["due"] < rec["window_s"]]
    device = dict(label, memory_peak_bytes=mem)
    out = {"correct": bool(seqs) and bad == 0 and K.passed(checks),
           "attempted": len(due),
           "failed": sum(r["shed"] is not None for r in due),
           "metrics": {k: {"value": v, "unit": cs["units"][k]}
                       for k, v in metrics.items()},
           "device": device}
    if tracing:
        t = rec["trace"]
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["checks"] = dict(
        checks, bad_requests={"value": bad, "limit": 0},
        checked_requests={"value": len(seqs), "limit": 1})
    return out


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import cell as C
    from chipbench import spec as S
    cs = S.cell(args.workload)
    try:
        label = C.check_device(cs["workload"]["chips"])
    except C.NoChip as e:
        print(f"[bench] FAIL: {e}", file=sys.stderr, flush=True)
        return 3
    tag = f"[bench {label['platform']} {label['kind']} x{label['count']}]"

    def log(msg):
        print(f"{tag} {msg}", flush=True)
    from repro.launch.serve import setup_compile_cache
    log(f"{args.workload} seed {args.seed}: compile cache "
        f"{setup_compile_cache()}")
    out = serve_and_check(cs, args.seed, args.seconds, bool(args.trace),
                          label, log, C.CompileCounter())
    for name, c in out["checks"].items():
        print(f"{tag} check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
