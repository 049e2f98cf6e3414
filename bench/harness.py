"""Build the engine for a cell and drive its closed loop.

Everything here runs the program as a deployment would: the engine is
built as ``launch/serve.py`` builds it, from the settings in the cell's
configuration file, and the loop calls only ``add_request`` and
``step``.  The harness keeps its own clock, counts and spans."""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from . import work

now = time.perf_counter


class CompileClock:
    """Counts programs handed to XLA (compiled, or loaded from the
    persistent cache: JAX times both as a backend compile), their
    seconds, and the persistent-cache hits among them, so each phase can
    report its own.  Compiled = programs - hits."""

    def __init__(self):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> Tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


def model_config(model: Dict[str, Any]):
    """The engine's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import LayerKind, ModelConfig

    if model["hidden_act"] != "silu":
        raise ValueError("only SwiGLU MLPs are mapped")
    return ModelConfig(
        name=model["name"], family="dense",
        num_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], d_ff=model["intermediate_size"],
        vocab_size=model["vocab_size"],
        qkv_bias=bool(model["attention_bias"]),
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        layer_pattern=(LayerKind("attn", "mlp"),),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        dtype=model["weights"]["dtype"])


def page_bytes(model: Dict[str, Any]) -> int:
    """Bytes of one KV page: K and V over every attention layer."""
    e = model["engine"]
    width = jax.numpy.dtype(e["kv_pool_dtype"]).itemsize
    return (2 * model["num_hidden_layers"] * e["page_size"]
            * model["num_key_value_heads"] * model["head_dim"] * width)


def pool_pages(model: Dict[str, Any], bytes_limit: int,
               weight_bytes: int) -> int:
    """Pages that fit beside the weights, less the stated headroom."""
    free = bytes_limit - weight_bytes - model["engine"]["headroom_bytes"]
    return max(0, free // page_bytes(model))


def build_engine(model: Dict[str, Any], mix: Dict[str, Any], params,
                 num_pages: int):
    from repro.serving.cache import CachePolicy
    from repro.serving.engine import DecodeEngine

    e = model["engine"]
    if e["prefix_cache"] != "CachePolicy()":
        raise ValueError("the harness maps only the default CachePolicy")
    return DecodeEngine(
        model_config(model), params, page_size=e["page_size"],
        num_pages=num_pages, backend=e["backend"],
        max_q=max(int(mix["clients"]), e["min_max_q"]),
        temperature=e["temperature"], fused=e["fused"],
        cache=CachePolicy())


@dataclasses.dataclass
class Req:
    client: int
    prompt: List[int]
    private: int
    max_new: int
    sent: float
    admit_s: float = 0.0
    first: Optional[float] = None
    last: Optional[float] = None
    done: Optional[float] = None
    reason: Optional[str] = None
    decoded: int = 0
    tokens: List[int] = dataclasses.field(default_factory=list)


class ClosedLoop:
    """Clients that each send their next request the moment the last
    one ends.  Send time is that moment, so a request that waits behind
    other admissions counts the wait in its time to first token."""

    def __init__(self, eng, traffic, shared: int):
        self.eng = eng
        self.traffic = traffic
        self.shared = shared
        self.reqs: List[Req] = []
        self.live: List[Req] = []
        self.ready: List[Tuple[int, float]] = []
        # per engine step: (host time, contexts of live rows, private
        # tokens of live rows) -- what the step's work is counted from
        self.steps: List[Tuple[float, List[int], List[int]]] = []
        self.delivered: List[float] = []

    def _on_token(self, req: Req, tok: int) -> None:
        t = now()
        if req.first is None:
            req.first = t
        req.last = t
        req.tokens.append(int(tok))
        self.delivered.append(t)

    def _on_done(self, req: Req, reason: str) -> None:
        req.done = now()
        req.reason = reason
        self.ready.append((req.client, req.done))

    def send(self, client: int, sent: float) -> Req:
        prompt, private, max_new = self.traffic.next(client)
        req = Req(client, prompt, private, max_new, sent)
        with jax.profiler.TraceAnnotation("bench.add_request"):
            t0 = now()
            self.eng.add_request(
                prompt, max_new=max_new,
                on_token=lambda _rid, tok, r=req: self._on_token(r, tok),
                on_done=lambda _rid, why, r=req: self._on_done(r, why))
            req.admit_s = now() - t0
        self.reqs.append(req)
        self.live.append(req)
        return req

    def step(self) -> None:
        ctx = [len(r.prompt) + r.decoded + 1 for r in self.live]
        priv = [c - self.shared for c in ctx]
        self.steps.append((now(), ctx, priv))
        with jax.profiler.TraceAnnotation("bench.step"):
            self.eng.step()
        with jax.profiler.TraceAnnotation("bench.bookkeeping"):
            for r in self.live:
                r.decoded += 1
            self.live = [r for r in self.live if r.done is None]

    def start(self) -> None:
        t = now()
        for c in range(self.traffic.clients):
            self.send(c, t)

    def run(self, until: float = float("inf"), steps: int = -1,
            send: bool = True, on_step=None) -> None:
        """Step until the host clock reaches ``until`` or ``steps`` steps
        have run, sending each finished client's next request."""
        while now() < until and steps != 0:
            steps -= 1
            self.step()
            ready, self.ready = self.ready, []
            if send:
                for c, t in ready:
                    self.send(c, t)
            if on_step is not None:
                on_step()


def prime(eng, prompts: List[List[int]], max_steps: int = 64) -> None:
    """Serve each prompt for one token, in turn, so that its KV stays in
    the prefix cache."""
    for p in prompts:
        done = []
        eng.add_request(p, max_new=1,
                        on_done=lambda rid, why: done.append(why))
        for _ in range(max_steps):
            if done:
                break
            eng.step()
        if done != ["done"]:
            raise RuntimeError(f"priming request ended {done!r}")


def pctl(values: List[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    of the sample at or below it."""
    v = sorted(values)
    k = max(0, -(-int(round(q * 1000)) * len(v) // 1000) - 1)
    return v[k]


def host_metrics(loop: ClosedLoop, w0: float, w1: float) -> Dict[str, Any]:
    """End-to-end numbers of the window ``[w0, w1)``, host clock."""
    sent = [r for r in loop.reqs if w0 <= r.sent < w1]
    ttft = [(r.first - r.sent) if r.first is not None else float("inf")
            for r in sent]
    fin = [r for r in loop.reqs
           if r.done is not None and w0 <= r.done < w1
           and r.reason == "done" and len(r.tokens) > 1]
    tpot = [(r.last - r.first) / (len(r.tokens) - 1) for r in fin]
    tokens = sum(1 for t in loop.delivered if w0 <= t < w1)
    failed = [r for r in sent if (r.reason not in (None, "done")
                                  or r.first is None)]
    return {"sent": sent, "finished": fin, "tokens": tokens,
            "ttft": ttft, "tpot": tpot, "failed": len(failed)}


def drain(loop: ClosedLoop, w0: float, w1: float,
          limit_s: float) -> Dict[str, Any]:
    """After the window closes: step on, sending nothing new, until every
    request sent in ``[w0, w1)`` has its first token or ``limit_s`` has
    passed, so that a late answer counts as late and not as failed.
    Returns the window's numbers (``host_metrics``)."""
    hm = host_metrics(loop, w0, w1)
    t0 = now()
    while any(r.first is None for r in hm["sent"]) and now() < t0 + limit_s:
        loop.run(steps=1, send=False)
        hm = host_metrics(loop, w0, w1)
    return hm


def trace_steps(loop: ClosedLoop, t0: float, t1: float):
    return [s for s in loop.steps if t0 <= s[0] < t1 and s[1]]


def step_work(model_dims, steps, shared: int, page: int, kv_bytes: int,
              tied: bool, peak: Dict[str, float]) -> Dict[str, Any]:
    """Over the given step records: the mean model FLOPs of one step,
    and the mean least time of one attention layer's kernel call."""
    if not steps:
        return {}
    f = least = 0.0
    bound: Dict[str, int] = {}
    for _, ctx, priv in steps:
        f += work.step_flops(model_dims, ctx, tied)
        fl, by = work.attention_work(model_dims, work.two_level(shared, priv),
                                     len(ctx), page, kv_bytes)
        t, which = work.least_time(fl, by, peak)
        least += t
        bound[which] = bound.get(which, 0) + 1
    n = len(steps)
    return {"step_flops": f / n, "attn_least_s": least / n,
            "attn_bound": max(bound, key=bound.get)}
