#!/usr/bin/env python3
"""Knee sweep for an open-loop cell: one engine, one window per offered
rate, rising, each window starting empty.

    python benchmarks/chip/sweep.py --workload <name> \
        --rates 0.4,0.5,0.6,0.7 --seconds 75 --seed <n>

For each rate it prints a table row and a JSON line: the offered rate,
the requests due in the window, those finished in it, ``slo_ok_pct``,
``ttft_p90_s``, ``tbt_p99_ms``, ``out_tok_per_s``, the backlog (due but
not yet admitted) at the window's middle and at its end, and the compiles
inside the window.  The traffic is the cell's own mix with only its rate
changed: the same schedule, the same scheduler and engine.  Before each
window the engine warms up that rate's merged cohorts, and after it
finishes what the window left in flight.

The knee (``knee``) is the highest rate at which the backlog at the
window's end is no larger than at its middle, so that the queue does not
grow, and ``ttft_p90_s`` is at most a quarter of the mix's TTFT limit.
The share of requests within both limits (``slo_ok_pct``) is printed but
does not decide: an iteration that carries a layer group of a
2-3k-token prompt takes about the mix's TBT limit, so that share swings
at every rate.  Give ``--seconds`` at least three times a request's mean
service time (mean output tokens x decode step), so that the slots have
filled by the window's middle.  The cell then offers 0.8 x the knee,
written into its traffic file as a number.
"""

import json
import sys
from pathlib import Path
from typing import List, Optional

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def backlog(rec: dict, t: float) -> int:
    return sum(r["due"] <= t and (r["admit"] is None or r["admit"] > t)
               for r in rec["requests"])


def knee(rows: List[dict], ttft_limit_s: float) -> Optional[float]:
    """The highest offered rate whose row kept its backlog from growing
    and its ttft_p90_s within a quarter of ``ttft_limit_s``."""
    ok = [r["rate_rps"] for r in rows
          if r["backlog_end"] <= r["backlog_mid"]
          and r.get("ttft_p90_s") is not None
          and r["ttft_p90_s"] <= ttft_limit_s / 4]
    return max(ok) if ok else None


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    from chipbench import cell as C
    from chipbench import spec as S
    cs = S.cell(args.workload)
    try:
        label = C.check_device(cs["workload"]["chips"])
    except C.NoChip as e:
        print(f"[sweep] FAIL: {e}", file=sys.stderr)
        return 3
    tag = f"[sweep {label['platform']} {label['kind']} x{label['count']}]"
    from repro.launch.serve import setup_compile_cache
    setup_compile_cache()
    compiles = C.CompileCounter()
    conf, mix = cs["config"], cs["traffic"]
    eng, sc = C.build(conf, args.seed)
    names = ["slo_ok_pct", "ttft_p90_s", "tbt_p99_ms", "out_tok_per_s"]
    print(f"{tag} {args.workload}: rate | due | finished | "
          + " | ".join(names) + " | backlog mid | backlog end", flush=True)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        C.warm_up(eng, sc, conf, dict(mix, rate_rps=rate), args.seconds)
        rec = C.run_window(eng, sc, conf, dict(mix, rate_rps=rate),
                           args.seed + i, args.seconds, compiles=compiles)
        rec.update(setup_s=0.0, peak_flops=1.0)
        m = C.metrics(names, rec)
        due = [r for r in rec["requests"] if r["due"] < args.seconds]
        row = {"rate_rps": rate, "due": len(due),
               "finished": sum(r["done"] for r in due), **m,
               "backlog_mid": backlog(rec, args.seconds / 2),
               "backlog_end": backlog(rec, args.seconds),
               "compiles_in_window": rec["compiles"]}
        rows.append(row)
        print(f"{tag} " + " | ".join(
            f"{v:.4g}" if isinstance(v, float) else str(v)
            for v in row.values()), flush=True)
        print(json.dumps(row), flush=True)
        C.drain(eng)
    k = knee(rows, mix["slo"]["ttft_s"])
    print(f"{tag} knee {k} req/s; 0.8 x knee = "
          f"{None if k is None else 0.8 * k}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
