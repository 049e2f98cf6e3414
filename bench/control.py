"""Readings that a cell's correctness limit is set from, on the chip.

    python3 -m bench.control --workload <cell> --seeds 1 2 ... \\
        --control-seeds 1 2 3 --seconds 15

For each seed, in one process: the cell's weights, engine, priming and
warm-up as in ``bench.run``, a short window at the cell's own load, and
the widest logit gap of the served tokens of the same sample
``bench.run`` would take (the program's reading).  For each control
seed, also the control's reading on the same sequences: the token the
float8 reference puts first, and its gap in the float32 reference.
Each reading is judged by the comparison ``bench.run`` makes
(``check.correct`` against the cell's limit): the program's should come
out correct, the control's not.  One JSON line per seed on standard
output.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

from bench import check, harness, spec, weights
from bench.run import DRAIN_S, BenchError, find_chips, import_program, \
    log, use_compile_cache
from bench.traffic import ClosedLoopTraffic


def readings(cell_name: str, seed: int, seconds: float, control: bool):
    cell = spec.workload(spec.benchmark(), cell_name)
    model = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    ref = spec.reference(model["reference"])
    limit = spec.limits(cell["name"])["max_logit_gap"]["limit"]
    dev = find_chips(cell["chips"])[0]
    params = weights.make(model, seed)
    pages = harness.pool_pages(model, mix, dev.memory_stats()["bytes_limit"],
                               weights.nbytes(params))
    traffic = ClosedLoopTraffic(mix, seed, model["vocab_size"])
    eng = harness.build_engine(model, mix, params, pages)
    harness.prime(eng, traffic.prime_prompts(mix["prime_chunk_tokens"]))
    loop = harness.ClosedLoop(eng, traffic, len(traffic.shared))
    loop.start()
    loop.run(steps=int(mix["warmup_steps"]))
    w0 = harness.now()
    loop.run(until=w0 + seconds)
    hm = harness.drain(loop, w0, w0 + seconds, DRAIN_S)
    del eng, loop.eng
    gc.collect()
    picks = check.sample(hm["finished"], seed)
    out = {"workload": cell_name, "seed": seed,
           "finished": len(hm["finished"]),
           "served_tokens": sum(len(r.tokens) for r in picks),
           "limit": limit}
    for name in ("program", "control") if control else ("program",):
        gap, _ = check.widest_gap(ref, params, model, picks,
                                  control=name == "control")
        out[name] = gap
        out[f"{name}_correct"] = check.correct(len(picks), gap, limit,
                                               hm["failed"])
    del params
    gc.collect()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    use_compile_cache()
    import_program()
    try:
        for seed in args.seeds:
            r = readings(args.workload, seed, args.seconds,
                         seed in args.control_seeds)
            print(json.dumps(r), flush=True)
            log(json.dumps(r))
    except BenchError as e:
        log(f"FAIL: {e}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
