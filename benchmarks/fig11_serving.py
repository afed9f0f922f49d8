"""Beyond-paper Fig 11: continuous-batching serving throughput/latency.

A Zipf-skewed, Poisson-arrival request stream with mixed generation lengths
is served three ways on the SAME decode path (launch/scheduler):

* ``static``     — whole-batch admission: a new wave only starts when every
                   slot is free, so short requests wait on the batch's
                   longest (the classic serving baseline).
* ``continuous`` — per-tick admit/retire into fixed decode slots over the
                   paged KV cache (vLLM-style in-flight batching).
* ``continuous+replan`` — same, plus the online placement loop: the decode
                   step's (L, E) expert-load feed drives the
                   PlacementController and accepted plans migrate live
                   params between ticks (bitwise-invisible in the stream —
                   tests/test_scheduler proves it differentially).

Skew arrives through the data like fig8: token embeddings cluster around
per-expert router centers and prompt tokens are drawn Zipf over the vocab,
so decode traffic genuinely imbalances the experts and the replan arm has
something to fix.  Reported per mode: tokens/sec, per-token p50/p99
latency, decode ticks, live replans.
"""
from __future__ import annotations

import json

from benchmarks.common import (FAKE_DEVICE_PLATFORM, record,
                               run_on_fake_devices, smoke_mode)

W = 4  # fake host devices -> 1x4 mesh
REPLAN_EVERY = 8

_SCRIPT = """
import dataclasses, json, time
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.models import lm
from repro.launch.scheduler import ContinuousBatcher
from repro.launch.serve_api import Request, ServeConfig

SLOTS, NREQ, EVERY = {slots}, {nreq}, {every}

# 8 experts on 4 ranks with small expert FFNs: the scale where the cost
# model's shadow-weight overhead is beatable and serve-time replans pay
cfg = reduced(get_config("fastmoe-gpt"), num_layers=2, d_model=64,
              max_experts=8)
cfg = dataclasses.replace(
    cfg, moe=dataclasses.replace(cfg.moe, d_expert_hidden=32))
E, DM, V = cfg.moe.num_experts, cfg.d_model, cfg.vocab_size
params = lm.init_params(jax.random.PRNGKey(0), cfg)

# fig8's skew-through-the-data idiom: embeddings cluster around router
# centers, cluster frequencies are Zipf, router columns ARE the centers
rng = np.random.RandomState(0)
centers = rng.normal(size=(E, DM)).astype(np.float32)
centers /= np.linalg.norm(centers, axis=1, keepdims=True)
zipf = 1.0 / (np.arange(E) + 1) ** 1.2
tok_cluster = rng.choice(E, size=V, p=zipf / zipf.sum())
params["embed"]["table"] = jnp.asarray(
    centers[tok_cluster] + 0.1 * rng.normal(size=(V, DM)).astype(np.float32))
params["layers"]["ffn"]["router"]["w"] = jnp.broadcast_to(
    jnp.asarray(centers.T * 4.0), (cfg.num_layers, DM, E)).astype(
        params["layers"]["ffn"]["router"]["w"].dtype)

# the request stream: Zipf token ids, mixed generation lengths (short
# chats + long completions — what head-of-line blocking punishes),
# Poisson arrivals measured in decode ticks
pv = 1.0 / (np.arange(V) + 1) ** 1.1
pv /= pv.sum()
sr = np.random.RandomState(1)
gens = [2 if i % 2 else 18 for i in range(NREQ)]
stream = [dict(id=i, prompt=sr.choice(V, size=int(sr.randint(4, 12)),
                                      p=pv).astype(np.int32),
               max_new_tokens=gens[i]) for i in range(NREQ)]
arrivals = np.cumsum(sr.poisson(0.5, size=NREQ))  # arrival tick per request

def serve(policy, replan_every):
    scfg = ServeConfig(slots=SLOTS, max_len=32, block_size=8, mesh="1x{mw}",
                       policy=policy, replan_every=replan_every)
    b = ContinuousBatcher(params, cfg, scfg)
    nxt = 0
    t0 = time.time()
    while nxt < NREQ or b.queue or any(s is not None for s in b.slots):
        while nxt < NREQ and arrivals[nxt] <= b.ticks:
            b.submit(Request(arrival=t0, **stream[nxt]))
            nxt += 1
        if b.step() == 0 and nxt < NREQ:
            b.ticks += 1  # idle tick while the stream is still arriving
    dt = time.time() - t0
    toks = sum(len(c.tokens) for c in b.completions)
    lats = sorted(l for c in b.completions for l in c.latencies[1:]) or [0.0]
    return dict(mode=policy if not replan_every else "continuous+replan",
                tok_s=toks / max(dt, 1e-9), ticks=b.ticks, tokens=toks,
                requests=len(b.completions), replans=b.replans,
                p50_ms=lats[len(lats) // 2] * 1e3,
                p99_ms=lats[min(len(lats) - 1, int(len(lats) * 0.99))] * 1e3)

rows = [serve("static", 0), serve("continuous", 0),
        serve("continuous", EVERY)]
assert rows[0]["tokens"] == rows[1]["tokens"] == rows[2]["tokens"]
assert rows[1]["ticks"] < rows[0]["ticks"], "continuous must save ticks"
print("RESULT " + json.dumps(rows))
"""


def run(quick: bool = False) -> list[dict]:
    # the replan arm needs enough decode slots that the modeled a2a savings
    # beat the shadow-weight cost (see the controller's cost model); smoke
    # only proves the three modes run and continuous beats static
    slots, nreq = (8, 24) if (quick or smoke_mode()) else (32, 120)
    script = _SCRIPT.format(w=W, mw=W, slots=slots, nreq=nreq,
                            every=REPLAN_EVERY)
    rows = json.loads(run_on_fake_devices(script, W).strip()
                      .split("RESULT ")[1])
    static, cont = rows[0], rows[1]
    if cont["tok_s"] <= static["tok_s"]:
        raise RuntimeError(
            f"continuous batching must beat static admission: "
            f"{cont['tok_s']:.1f} <= {static['tok_s']:.1f} tok/s "
            f"(ticks {cont['ticks']} vs {static['ticks']})")
    for r in rows:
        r["slots"] = slots
        r["backend"] = FAKE_DEVICE_PLATFORM
        record({"bench": "fig11", **r})
        print(f"fig11,{r['mode']},{r['tok_s']:.1f} tok/s,"
              f"p50={r['p50_ms']:.1f}ms p99={r['p99_ms']:.1f}ms "
              f"ticks={r['ticks']} replans={r['replans']}")
    return rows
