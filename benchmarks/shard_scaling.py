"""Shard-scaling benchmark: decode TPOT + makespan vs device count.

Sweeps fake-device counts (1 / 2 / 4 by default) and, for each, runs
the SPMD sharded decode engine (``distributed/``) on a ``Dx1`` mesh
over the same doc-QA workload in a **subprocess** (the device count is
fixed at jax backend init, so every count needs its own process).

Fake host devices SERIALIZE on the local CPU cores and pay a
per-step multi-device dispatch cost a real mesh does not, so raw
wall-clock at ``D > 1`` measures emulation overhead, not scaling.
Each child therefore reports two latencies:

* ``wall_tpot_ms`` — raw warm-pass wall per decode step (pass 0 runs
  calibrated/blocking to collect per-step timings, fits the cost
  model, then pass 1 is timed with async dispatch).  A regression
  canary only: it grows ~linearly in D by construction of the
  emulation.
* ``model_step_us`` — the cost model's prediction of the per-step
  attention + merge time on a REAL mesh (heaviest shard's HBM/grid
  terms + sparse-merge wire/launch,
  ``DecodeEngine.predicted_step_seconds``), evaluated under the
  DATASHEET hardware spec so the number is comparable across child
  processes (online fits reject decode-steady features as
  unidentifiable — see ``CostModel.fit``).

The parent projects a real-mesh TPOT from the two: the dense
(FFN/unembed/dispatch) base cost is device-count-independent — the
compiled per-device program is identical across D — so

    ``tpot_ms(D) = wall_tpot_ms(1dev) + model_step_us(D)/1e3
                                      - model_step_us(1dev)/1e3``

i.e. the measured single-device step wall shifted by the model's
per-shard attention/merge delta.  ``tpot_vs_1dev`` (the CI gate) is
computed from this projection; ``wall_tpot_vs_1dev`` keeps the raw
ratio visible.  With replication promoting the hot shared prefix the
smoke-scale delta is ~zero (no merge rows, same local reads); on the
``longdoc`` preset sequence-splitting the prefix makes the projection
strictly BEAT one device (per-shard HBM ~1/D, small sparse merge).

``python -m benchmarks.shard_scaling [--preset smoke] [--devices 1,2,4]``
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

OUT = os.environ.get("BENCH_SHARD_OUT", "BENCH_shard.json")

CHILD = textwrap.dedent("""\
    import json, os, sys, time
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=%(devices)d")
    import jax
    from repro.configs import smoke_config
    from repro.models import transformer as T
    from repro.serving.engine import DecodeEngine
    from repro.distributed import decode_mesh

    DEV = %(devices)d
    DOC_LEN = %(doc_len)d
    REQUESTS = %(requests)d
    MAX_NEW = %(max_new)d
    PAGE = 16

    cfg = smoke_config("%(arch)s")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    doc = list(range(10, 10 + DOC_LEN))
    eng = DecodeEngine(cfg, params, page_size=PAGE, num_pages=%(pages)d,
                       backend="%(backend)s", max_q=max(REQUESTS, 8),
                       temperature=0.0, fused=True,
                       mesh=decode_mesh(DEV, 1),
                       seq_split_pages=2 if DEV > 1 else 0,
                       replicate=True, calibrate=True)
    hw0 = eng.cost_model.hw      # datasheet spec: cross-child comparable
    passes = []
    for pno in range(2):
        prompts = [doc + [200 + 16 * pno + 4 * i + j for j in range(4)]
                   for i in range(REQUESTS)]
        for p in prompts:
            eng.add_request(p, max_new=MAX_NEW)
        eng.step()                       # absorb prefill + first compile
        steps0 = eng.stats["steps"]
        t0 = time.perf_counter()
        while eng.has_work():
            eng.step()
        eng.flush_tokens()
        jax.block_until_ready(eng.pool.k)
        wall = time.perf_counter() - t0
        steps = max(eng.stats["steps"] - steps0, 1)
        passes.append(dict(wall_s=wall, steps=steps,
                           tpot_ms=wall / steps * 1e3))
        if pno == 0:
            # pass 0 ran calibrated (each dispatch blocked -> true step
            # seconds); install the fit, then time pass 1 with async
            # dispatch -- the serving configuration being benchmarked
            eng.recalibrate(min_samples=4)
            eng.calibrate = False
    sp = eng._sharded_plans.get(0)
    ps = sp.stats()
    hw = eng.cost_model.hw
    out = dict(devices=DEV, wall_tpot_ms=passes[1]["tpot_ms"],
               steps=passes[1]["steps"],
               model_step_us=eng.predicted_step_seconds(hw=hw0) * 1e6,
               compile_count=eng.fused_cache_size,
               bucket_signatures=len(eng.bucket_signatures),
               replans=eng.stats["replans"],
               makespan_us=sp.makespan * 1e6,
               merge_cost_us=sp.merge_cost * 1e6,
               local_makespan_us=(sp.makespan - sp.merge_cost) * 1e6,
               seq_splits=sp.seq_splits,
               replicated_nodes=ps["replicated_nodes"],
               merge_rows=ps["merge_row_count"],
               replica_promotions=eng.stats["replica_promotions"],
               calibrated=eng.cost_model.calibrated,
               calibrations=eng.stats["calibrations"],
               fitted_hbm_gbps=hw.hbm_bw / 1e9,
               fitted_ici_gbps=hw.ici_bw / 1e9,
               shard_occupancy=eng.pool.shard_occupancy())
    print("RESULT " + json.dumps(out))
""")


def run_child(devices: int, arch: str, backend: str, doc_len: int,
              requests: int, max_new: int, pages: int = 1024) -> dict:
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.abspath(src) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)          # the child pins its own
    # the child emulates the mesh on fake CPU devices; it must never
    # ask for an accelerator the parent process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    code = CHILD % dict(devices=devices, arch=arch, backend=backend,
                        doc_len=doc_len, requests=requests,
                        max_new=max_new, pages=pages)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=1200)
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"child ({devices} devices) failed:\n"
                       f"{r.stdout[-1500:]}\n{r.stderr[-3000:]}")


def main() -> None:
    from benchmarks.common import emit

    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=["smoke", "full", "longdoc"],
                    default="smoke")
    ap.add_argument("--devices", default="1,2,4")
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--backend", default="codec-xla")
    args, _ = ap.parse_known_args()

    # longdoc: one long shared document per request batch — the regime
    # where sequence-splitting the prefix across shards (parallel page
    # reads) beats a single device outright, replication stays off
    # (CostModel.replicate_gain goes negative), and the sparse merge
    # carries the whole batch
    presets = {"smoke": (96, 4, 8, 1024),
               "full": (256, 8, 16, 1024),
               "longdoc": (2048, 4, 16, 2048)}
    doc_len, requests, max_new, pages = presets[args.preset]
    counts = [int(x) for x in args.devices.split(",") if x]
    result = {"arch": args.arch, "backend": args.backend,
              "preset": args.preset,
              "config": dict(doc_len=doc_len, requests=requests,
                             max_new=max_new),
              "tpot_note": ("tpot_ms projects real-mesh TPOT: measured "
                            "1-device step wall + the calibrated model's "
                            "per-shard attention/merge delta (fake host "
                            "devices serialize, so raw wall_tpot_ms at "
                            "D>1 measures emulation overhead only)"),
              "sweep": []}
    base_wall = base_model = None
    for n in counts:
        row = run_child(n, args.arch, args.backend, doc_len, requests,
                        max_new, pages)
        if base_wall is None:
            base_wall = row["wall_tpot_ms"]
            base_model = row["model_step_us"]
        row["tpot_ms"] = (base_wall
                          + (row["model_step_us"] - base_model) / 1e3)
        row["tpot_vs_1dev"] = row["tpot_ms"] / max(base_wall, 1e-9)
        row["wall_tpot_vs_1dev"] = row["wall_tpot_ms"] / max(base_wall,
                                                             1e-9)
        result["sweep"].append(row)
        emit("shard_scaling", f"{n}dev",
             us_per_call=row["tpot_ms"] * 1e3,
             tpot_ms=row["tpot_ms"],
             wall_tpot_ms=row["wall_tpot_ms"],
             model_step_us=row["model_step_us"],
             makespan_us=row["makespan_us"],
             merge_cost_us=row["merge_cost_us"],
             seq_splits=row["seq_splits"],
             compiles=row["compile_count"])
    with open(OUT, "w") as f:
        json.dump(result, f, indent=1)
    span = ", ".join(f"{r['devices']}dev {r['tpot_ms']:.3f}ms "
                     f"(x{r['tpot_vs_1dev']:.2f}, model "
                     f"{r['model_step_us']:.1f}us, merge "
                     f"{r['merge_cost_us']:.2f}us)"
                     for r in result["sweep"])
    print(f"# wrote {OUT}: projected TPOT {span}")


if __name__ == "__main__":
    main()
