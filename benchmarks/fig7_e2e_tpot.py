"""Paper Fig. 7: end-to-end TPOT, CoDec engine vs the vLLM-analogue
(same engine, FlashDecoding backend).

CPU wall-time on the smoke model (real execution, interpret kernels)
plus the modeled full-scale TPOT decomposition (attention makespan from
the cost model + roofline FFN time) for the paper's Qwen3-4B.
"""

from __future__ import annotations

import time

import jax

from benchmarks.common import emit, paper_cost_model, timeit
from repro.configs import get_config, smoke_config
from repro.core import plan as plan_mod, tree as tree_mod
from repro.core.cost_model import HBM_BW
from repro.models import transformer as T
from repro.serving.engine import DecodeEngine

PAGE = 64


def measured_smoke() -> None:
    cfg = smoke_config("qwen2.5-14b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    doc = list(range(10, 10 + 96))
    prompts = [doc + [200 + 4 * i + j for j in range(4)] for i in range(4)]
    for backend, fused in (("codec-xla", False), ("codec-xla", True),
                           ("flash", False)):
        eng = DecodeEngine(cfg, params, page_size=16, num_pages=1024,
                           backend=backend, max_q=8, fused=fused)
        for p in prompts:
            eng.add_request(p, max_new=6)
        # wall-clock TPOT with a terminal device sync, started after the
        # first step so prefill + cold jit compiles are excluded: on the
        # fused path the engine's host timers (decode_dispatch_time,
        # decode_sync_time) cover only dispatch + boundary syncs (async
        # compute surfaces at the block)
        eng.step()
        t0 = time.perf_counter()
        eng.run(6)
        eng.flush_tokens()
        jax.block_until_ready(eng.pool.k)
        steps = eng.stats["steps"] - 1
        tpot_ms = (time.perf_counter() - t0) / max(steps, 1) * 1e3
        emit("fig7_smoke", backend + ("-fused" if fused else ""),
             us_per_call=tpot_ms * 1e3,
             tpot_ms=tpot_ms, steps=eng.stats["steps"],
             plan_s=eng.stats["plan_time"])


def modeled_full() -> None:
    """Full Qwen3-4B TPOT model: attention makespan + memory-bound rest."""
    cfg = get_config("qwen3-4b")
    cm = paper_cost_model(PAGE)
    n_attn = cfg.num_layers
    # non-attention per-step time: stream active params once (memory bound)
    ffn_bytes = cfg.param_count() * 2
    t_rest = ffn_bytes / HBM_BW
    for ctx in (30_000, 60_000, 120_000):
        f = tree_mod.two_level(32, ctx // PAGE * PAGE, 2048, PAGE)
        plan_mod.assign_dense_pages(f)
        pc = plan_mod.build_plan(f, cm, 8, 256, 8192)
        pf = plan_mod.flash_plan(f, cm, 8, 256, 8192)
        tpot_c = n_attn * pc.makespan + t_rest
        tpot_f = n_attn * pf.makespan + t_rest
        emit("fig7_model", f"ctx{ctx}",
             tpot_codec_ms=tpot_c * 1e3, tpot_flash_ms=tpot_f * 1e3,
             speedup=tpot_f / tpot_c)


def main() -> None:
    measured_smoke()
    modeled_full()


if __name__ == "__main__":
    main()
