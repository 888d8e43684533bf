#!/usr/bin/env python3
"""Readings for a cell's correctness limit, on the chip, at the cell's
own size and load: for each seed, one window of the cell's traffic, then
the reference over the finished requests it samples, reading both the
program's logit gaps and the control's (the same reference with every
matmul in float8, whose first choice at each position is judged), and
judging each side by the benchmark's own comparison
(``chipbench.check``) against the configuration's limits.

    python benchmarks/chip/control.py --workload <name> --seconds 20 \
        --seeds 11,12,13

Each seed prints one JSON line: the seed, and for the served tokens
("served") and the control's first choices ("control") the widest gap,
its 99th percentile, the mean, the share of tokens that are not the
reference's first choice, each compared number beside its limit, and
``correct``.  The control has to come out not correct on every seed.
The limit in the configuration file lies between the largest program
gap over a dozen seeds or more and the smallest control gap.  The
benchmark's own runs never run the control."""

import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    from chipbench import cell as C
    from chipbench import check as K
    from chipbench import reference as R
    from chipbench import spec as S
    cs = S.cell(args.workload)
    try:
        label = C.check_device(cs["workload"]["chips"])
    except C.NoChip as e:
        print(f"[control] FAIL: {e}", file=sys.stderr)
        return 3
    tag = f"[control {label['platform']} {label['kind']} x{label['count']}]"
    from repro.launch.serve import setup_compile_cache
    setup_compile_cache()
    conf, mix = cs["config"], cs["traffic"]
    for seed in (int(s) for s in args.seeds.split(",")):
        eng, sc = C.build(conf, seed)
        C.warm_up(eng, sc, conf, mix, args.seconds)
        rec = C.run_window(eng, sc, conf, mix, seed, args.seconds)
        C.finish_late(eng, rec, mix["ref_requests"], 60.0)
        ids = C.sample_finished(eng, rec, seed, mix["ref_requests"])
        seqs = [(eng.prompts[i].tolist(), list(eng.outputs[i]))
                for i in ids]
        bad = C.served_tokens_ok(eng, rec)
        del eng
        gc.collect()
        row = {"seed": seed, "requests": len(seqs), "bad_requests": bad}
        if seqs:
            g = R.gaps(conf, seed, seqs, sc.max_len,
                       control=bool(args.control), rows=mix["ref_requests"])
            for side, per_seq in g.items():
                summ = R.summary(per_seq)
                compared = K.checks(conf, summ)
                ok = K.passed(compared) and (side == "control" or bad == 0)
                row[side] = dict(summ, checks=compared, correct=ok)
        print(f"{tag} {json.dumps(row)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
