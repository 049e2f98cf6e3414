"""Tests of the benchmark's own code (``bench/``), on the CPU.

    PYTHONPATH=src python -m pytest -q tests/benchmark

They cover the trace reduction (on an excerpt recorded on a v5e), the
FLOP and byte counters against hand counts, the traffic generator, the
entry's refusal to measure without a TPU, a rehearsal of every cell at
smoke size through the harness's own functions (which also checks that
the fused step is still the program the trace reduction looks for), the
correctness check failing when a served token is altered where it is
produced, the float8 control failing the smoke check, and a compile of
PAC for a described v5e at qwen2.5-14b's heads (which checks the
kernel's trace name).
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import check, harness, spec, trace, weights, work  # noqa: E402
from bench.traffic import ClosedLoopTraffic  # noqa: E402

EXCERPT = os.path.join(HERE, "data", "trace_excerpt.json")
CELLS = [(w["name"], w["config"], w["traffic"])
         for w in spec.benchmark()["workloads"]]


# ---------------------------------------------------------- smoke sizes
def smoke_model(config):
    """A configuration at smoke widths: the same engine settings,
    weights recipe and reference."""
    m = copy.deepcopy(config)
    m.update(hidden_size=64, intermediate_size=96, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=257)
    m["engine"]["headroom_bytes"] = 0
    return m


def smoke_mix(mix):
    """A mix at smoke size: 4 clients, a 64-token shared prefix (primed
    in two chunks) where the mix shares one, short prompts and answers."""
    x = copy.deepcopy(mix)
    shared = 64 if x["shared_prefix_tokens"] else 0
    x.update(clients=4, shared_prefix_tokens=shared,
             prime_chunk_tokens=32 if shared else 0,
             private_prompt_tokens=[8, 16] if shared else [16, 32],
             answer_tokens={"min": 3, "max": 9}, warmup_steps=6)
    return x


# ---------------------------------------------------------------- trace
def _excerpt():
    with open(EXCERPT) as f:
        return [tuple(e) for e in json.load(f)]


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_and_gaps_on_hand_events():
    ev = [("op", "/device:TPU:0", "a", 0, 10),
          ("op", "/device:TPU:0", "b", 5, 10),     # overlaps a
          ("op", "/device:TPU:0", "c", 40, 20),
          ("module", "/device:TPU:0", "jit_step(1)", 0, 15),
          ("module", "/device:TPU:0", "jit_step(1)", 40, 20),
          ("host", "python", "bench.step", 10, 20),
          ("host", "python", "bench.add_request", 30, 25)]
    win = (0, 100)
    assert trace.busy_ns(ev, "/device:TPU:0", win) == 35
    # gaps: 15-40 (mid 27.5: bench.step), 60-100 (mid 80: none)
    assert trace.idle_gaps(ev, "/device:TPU:0", win) == [
        ["none", 40e-9], ["bench.step", 25e-9]]
    s = trace.summarize(ev, "jit_step", ["c"], win)
    assert trace.op_time([("op", "d", "pac.12", 0, 5),
                          ("op", "d", "space.1", 0, 7)], ["pac"]) == (1, 5)
    assert (s["step_runs"], s["kernel_runs"]) == (2, 1)
    assert s["step_s"] == pytest.approx(35e-9)
    assert s["busy_s"] == pytest.approx(35e-9)
    assert s["window_s"] == pytest.approx(100e-9)


def test_reduction_of_recorded_v5e_trace():
    """An excerpt of a v5e trace (three engine steps of a small model
    through the fused codec-pallas step): busy time is below the window,
    every step program run is found, and the kernel's events lie inside
    the step programs' time."""
    ev = _excerpt()
    names = spec.kernels("codec-pallas")
    hosts = [(s, s + d) for k, _, _, s, d in ev if k == "host"]
    win = (min(s for s, _ in hosts), max(e for _, e in hosts))
    s = trace.summarize(ev, names["step_program"], names["attention_ops"],
                        win)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["step_runs"] >= 2
    assert 0 < s["kernel_s"] < s["step_s"]
    assert s["kernel_runs"] % s["step_runs"] == 0
    assert s["device_ops"] and s["idle_gaps"]
    assert {n for n, _ in s["idle_gaps"]} <= {
        "bench.step", "bench.add_request", "bench.bookkeeping", "none"}


# ------------------------------------------------------------- counters
N = {"layers": 2, "d": 8, "hq": 4, "hkv": 2, "hd": 4, "ff": 16, "vocab": 10}


def test_step_flops_by_hand():
    # per token: layers * (8*(4+4)*4 + 4*4*8 + 3*8*16) + 8*10
    per_tok = 2 * (256 + 128 + 384) + 80
    assert work.matmul_params_per_token(N, True) == per_tok
    # two rows with contexts 5 and 7: 2*per_tok*2 + 4*4*4*12*2
    assert work.step_flops(N, [5, 7]) == 2 * per_tok * 2 + 4 * 4 * 4 * 12 * 2


def test_two_level_forest_pages_and_bytes_by_hand():
    # shared 64 tokens (4 pages of 16) read by 3 rows; privates 20, 16, 5
    nodes = work.two_level(64, [20, 16, 5])
    assert nodes == [(64, 3), (20, 1), (16, 1), (5, 1)]
    pages, row_tokens = work.forest_pages(nodes, 16)
    assert pages == 4 + 1 + 1 + 0                 # partial pages are tails
    assert row_tokens == 64 * 3 + 16 + 16
    flops, nbytes = work.attention_work(N, nodes, rows=3, page=16,
                                        kv_bytes=4)
    assert flops == 4 * 4 * 4 * (64 * 3 + 32)
    kv = 2 * 6 * 16 * 2 * 4 * 4                   # K and V, 6 pages, f32
    qo = 3 * 4 * (4 * 2 + (4 + 2) * 4)
    assert nbytes == kv + qo
    # per-request reads would be 3x the shared pages: sharing is counted
    _, unshared = work.attention_work(N, [(84, 1), (80, 1), (69, 1)],
                                      rows=3, page=16, kv_bytes=4)
    assert unshared > nbytes


def test_least_time_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000, 50, peak) == (10.0, "compute")
    assert work.least_time(100, 50, peak) == (5.0, "bandwidth")


def test_peaks_refuse_an_unknown_device():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("cpu")


# ------------------------------------------------------------ traffic
@pytest.mark.parametrize("mix", ["docqa-4k", "chat-unshared"])
def test_generator_is_seeded_and_keeps_to_its_mix(mix):
    m = spec.traffic(mix)
    big = 2**31 + 12345

    def draw(seed):
        t = ClosedLoopTraffic(m, seed, 1000)
        return t, [t.next(c % t.clients) for c in range(3 * t.clients)]

    t, a = draw(big)
    _, b = draw(big)
    _, c = draw(big + 1)
    assert a == b and a != c
    assert len(t.shared) == m["shared_prefix_tokens"]
    lo, hi = m["answer_tokens"]["min"], m["answer_tokens"]["max"]
    for prompt, private, max_new in a:
        assert prompt[:len(t.shared)] == t.shared
        assert private in m["private_prompt_tokens"]
        assert len(prompt) == len(t.shared) + private
        assert lo <= max_new <= hi
    # every seed asks for the same work per round, in another order
    for i in (1, 2):
        assert (sorted(x[i] for x in a[:t.clients])
                == sorted(x[i] for x in c[:t.clients]))


def test_priming_covers_the_shared_prefix_in_chunks():
    t = ClosedLoopTraffic(spec.traffic("docqa-4k"), 3, 1000)
    ps = t.prime_prompts(2048)
    assert [len(p) for p in ps] == [2048, 4096] and ps[-1] == t.shared
    assert ClosedLoopTraffic(spec.traffic("chat-unshared"), 3,
                             1000).prime_prompts(0) == []


def test_pool_holds_each_cell_worst_case():
    """At v5e's bytes_limit the stated headroom leaves each cell's pool
    at least the pages its mix can hold at once (no preemption)."""
    limit = 16_909_336_576          # bytes_limit of one v5e, 16 GiB HBM
    for _, cfg, mix in CELLS:
        m = spec.config(cfg)
        n = weights.dims(m)
        params = (n["vocab"] * n["d"] * (1 if m["tie_word_embeddings"]
                                          else 2)
                  + n["layers"] * (
                      work.matmul_params_per_token(n, True)
                      - n["d"] * n["vocab"]) // n["layers"])
        t = ClosedLoopTraffic(spec.traffic(mix), 0, n["vocab"])
        need = t.worst_case_pages(m["engine"]["page_size"])
        assert harness.pool_pages(m, limit, 2 * params) >= need, (cfg, mix)


# ------------------------------------------------------------ entry
def test_entry_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0][0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# ------------------------------------------------------ CPU rehearsal
_COMPILED: list = []     # names of the programs compiled in this process


def _compiled_names():
    """Start recording the programs XLA compiles (once per process), and
    return the record."""
    import jax
    if not _COMPILED:
        _COMPILED.append(None)

        def on_duration(name, secs, fun_name="", **_):
            if name == "/jax/core/compile/backend_compile_duration":
                _COMPILED.append(fun_name)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
    return _COMPILED


def _module_name(fun_name):
    """The name of a compiled program as a device trace shows it:
    ``jit(step)`` runs as module ``jit_step``."""
    return fun_name.replace("(", "_").replace(")", "")


class _CpuChip:
    """A CPU device standing in for the chip in a rehearsal."""

    def __init__(self, dev):
        self._dev = dev
        self.platform, self.device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {"bytes_limit": 64 << 20, "peak_bytes_in_use": 1}


def _rehearse(monkeypatch, cell, config, mix, seed, fault=None):
    """Drive ``bench.run.run`` for one cell at smoke size on the CPU,
    with only the chip check replaced."""
    import jax
    from bench import run as run_mod

    monkeypatch.setattr(spec, "config",
                        lambda name, f=spec.config: smoke_model(f(name)))
    monkeypatch.setattr(spec, "traffic",
                        lambda name, f=spec.traffic: smoke_mix(f(name)))
    monkeypatch.setattr(run_mod, "use_compile_cache", lambda: None)
    monkeypatch.setattr(run_mod, "find_chips",
                        lambda n: [_CpuChip(jax.devices()[0])])
    if fault is not None:
        from repro.serving import sampler
        monkeypatch.setattr(sampler, "sample", fault(sampler.sample))
    args = types.SimpleNamespace(workload=cell, seed=seed, seconds=4.0,
                                 trace=0)
    jax.clear_caches()
    compiled = _compiled_names()
    first = len(compiled)
    r = run_mod.run(args)
    return r, [_module_name(n) for n in compiled[first:]]


@pytest.mark.parametrize("cell,config,mix", CELLS)
def test_rehearse_cell_on_cpu(monkeypatch, cell, config, mix):
    r, programs = _rehearse(monkeypatch, cell, config, mix, 2**31 + 99)
    assert r["correct"] is True, r
    assert r["attempted"] > 0 and r["failed"] == 0
    want = [m["name"] for m in spec.benchmark()["end_to_end"]]
    assert list(r["metrics"]) == want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    # the fused step still runs as the program whose device time
    # decode_step_ms and decode_mfu read from the trace
    step = spec.kernels(spec.config(config)["engine"]["backend"])[
        "step_program"]
    assert step in programs, sorted(set(programs))


def test_control_readings_are_judged_as_runs_are(monkeypatch):
    """``bench.control`` at smoke size: the program's reading comes out
    correct by the comparison ``bench.run`` makes, and the control's
    verdict is that same comparison of its own reading."""
    import jax
    from bench import control

    cell, _, _ = CELLS[-1]
    monkeypatch.setattr(spec, "config",
                        lambda name, f=spec.config: smoke_model(f(name)))
    monkeypatch.setattr(spec, "traffic",
                        lambda name, f=spec.traffic: smoke_mix(f(name)))
    monkeypatch.setattr(control, "find_chips",
                        lambda n: [_CpuChip(jax.devices()[0])])
    r = control.readings(cell, 2**31 + 5, 2.0, control=True)
    assert r["finished"] > 0 and r["served_tokens"] > 0
    assert r["program_correct"] is True, r
    assert r["control_correct"] is check.correct(1, r["control"],
                                                 r["limit"], 0)


def _alter_even_tokens(sample):
    """A fault where tokens are produced: every greedy token with an even
    id is replaced by the next id."""
    import jax.numpy as jnp

    def broken(logits, key, temperature=0.0, top_k=0):
        t = sample(logits, key, temperature, top_k)
        return jnp.where(t % 2 == 0, (t + 1) % logits.shape[-1], t
                         ).astype(t.dtype)
    return broken


@pytest.mark.parametrize("cell,config,mix", CELLS)
def test_altered_token_is_not_correct(monkeypatch, cell, config, mix):
    r, _ = _rehearse(monkeypatch, cell, config, mix, 7,
                     fault=_alter_even_tokens)
    assert r["correct"] is False
    c = r["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]


def test_smoke_readers_find_their_numbers():
    """Every per-layer reader reads a context the harness builds, and
    returns nothing where there is nothing to read."""
    tr = {"window_s": 2.0, "busy_s": 1.5, "step_runs": 10, "step_s": 0.3,
          "kernel_runs": 20, "kernel_s": 0.1}
    ctx = {"trace": tr, "peak": spec.peaks("TPU v5 lite"),
           "prompt_tokens": 1000, "prefill_tokens": 100,
           "admit_s": [0.1, 0.3],
           "work": {"step_flops": 2e9, "attn_least_s": 1e-3}}
    got = {m["name"]: spec.layer_metric(m["name"])(ctx)
           for m in spec.benchmark()["per_layer"]}
    assert got["prompt_reuse_share"] == pytest.approx(90.0)
    assert got["admit_ms_per_request"] == pytest.approx(200.0)
    assert got["decode_step_ms"] == pytest.approx(30.0)
    assert got["device_idle_share"] == pytest.approx(25.0)
    assert got["attn_roofline"] == pytest.approx(20.0)
    assert got["decode_mfu"] == pytest.approx(
        100 * 2e10 / (0.3 * 197e12))
    empty = dict(ctx, trace=dict(tr, step_runs=0, kernel_runs=0),
                 work={}, prompt_tokens=0, admit_s=[])
    for m in spec.benchmark()["per_layer"]:
        if m["name"] != "device_idle_share":
            assert spec.layer_metric(m["name"])(empty) is None, m["name"]


# ------------------------------------------- described-chip compile
@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e (the TPU library loads here, inside
    a test, never while modules are imported)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-chip compile cannot be read back from the cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def test_pac_compiles_for_v5e_at_qwen2_5_14b_heads(one_chip,
                                                   no_compile_cache):
    """PAC over the docqa-4k forest (16 rows under a 4096-token
    document, 384 private tokens each) at qwen2.5-14b's 40 q / 8 kv
    heads x 128, ``max_q`` 16, f32 pool of page 16, compiled by the TPU
    compiler; the kernel is named as the trace reduction expects."""
    import jax
    import jax.numpy as jnp
    from repro.core import cost_model, plan as plan_mod, tree as tree_mod
    from repro.kernels import ops

    m = spec.config("qwen2.5-14b-s12")
    rows, page = 16, m["engine"]["page_size"]
    hq, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    forest = tree_mod.two_level(rows, 4096, 384, block_size=page)
    num_pages = plan_mod.assign_dense_pages(forest) + 8
    cm = cost_model.CostModel(hq, hkv, hd, page_size=page)
    plan = plan_mod.bucket_plan(plan_mod.build_plan(
        forest, cm, num_lanes=2, max_q=rows), rows)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = sds((rows, hq, hd), jnp.bfloat16)
    pool = sds((num_pages, page, hkv, hd), jnp.float32)
    pa = jax.tree.map(lambda a: sds(np.shape(a), np.asarray(a).dtype),
                      ops.plan_arrays(plan))
    text = ops.codec_partials_arrays.lower(
        q, pool, pool, pa, rows, impl="pallas", interpret=False
    ).compile().as_text()
    assert "tpu_custom_call" in text
    names = spec.kernels("codec-pallas")["attention_ops"]
    assert any(f"%{n}." in text for n in names), names


# ------------------------------------------------------------ control
@pytest.mark.parametrize("cell", [c[0] for c in CELLS])
def test_float8_control_fails_the_cell_limit(cell):
    """The control -- the plain reference computed with float8 operands
    in the program's place -- reads a widest gap above the cell's limit
    on a model small enough for the CPU (vocabulary 32768, width 512,
    4 layers, the cell's weights recipe), while the float32 reference's
    own greedy tokens read 0."""
    m = smoke_model(spec.config(
        spec.workload(spec.benchmark(), cell)["config"]))
    m.update(vocab_size=32768, hidden_size=512, intermediate_size=1024,
             num_hidden_layers=4, num_attention_heads=4,
             num_key_value_heads=2, head_dim=64)
    ref = spec.reference(m["reference"])
    params = weights.make(m, 11)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, m["vocab_size"], 300).tolist()
    tail = rng.integers(0, m["vocab_size"], 255).tolist()
    seq = prompt + tail
    rows = list(range(len(prompt) - 1, len(seq)))
    greedy = ref.logits(params, m, seq, rows).argmax(-1)
    # served tokens = the teacher-forced continuation, so the context of
    # every row is ``seq``; the reference's own choice there reads 0
    lg = ref.logits(params, m, seq, rows)
    assert float((lg.max(-1) - lg[np.arange(len(rows)), greedy]).max()) == 0
    ctl = float(check.gaps(ref, params, m, prompt, tail + [0],
                           control=True).max())
    limit = spec.limits(cell)["max_logit_gap"]["limit"]
    assert ctl > limit
    assert check.correct(1, ctl, limit, 0) is False
    assert check.correct(1, 0.0, limit, 0) is True
