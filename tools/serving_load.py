"""Seeded open-loop load generator for the serving tier.

Drives an in-process InferenceServer (CPU, tiny fc model) with a
seeded Poisson arrival stream and reports goodput vs offered load and
the latency distribution of admitted requests.

stdout contract (gated in tools/ci.sh): EXACTLY ONE
JSON line; progress goes to stderr.  Headline fields:

    {"metric": "serving_goodput", "value": <goodput_qps>, "unit":
     "req/s", "offered_qps": ..., "capacity_qps": ..., "p50_ms": ...,
     "p99_ms": ..., "deadline_ms": ..., "admitted": N, "ok": N,
     "shed": N, "expired": N, "failed_over": N, "seed": N, ...}

Modes:
    --mode fixed       open loop at --qps
    --mode overload2x  measure single-replica capacity closed-loop,
                       then drive 2x that: the ISSUE 6 acceptance
                       shape (shedding keeps admitted p99 within the
                       deadline while goodput stays >= 80% of
                       capacity)
    --mode decode      ISSUE 7: open-loop RAGGED-length LLM decode
                       streams (seeded geometric prompt-length
                       distribution) through serving.DecodeServer —
                       continuous decode batching over the paged
                       KV-cache; reports tokens/s goodput and
                       inter-token p99 NEXT TO the request-level rows,
                       plus the zero-page-leak accounting verdict.
                       --disagg-prefill N (ISSUE 14) adds N
                       disaggregated prefill-tier replicas and the
                       JSON line grows the page-list handoff block
                       (offered/adopted/lost/latency + in-transit
                       zero verdict; ci.sh 5g gates it).

Cold-start metrics (ROADMAP item 5): every mode's JSON line carries
``time_to_first_batch_s`` (server start -> first completed request,
measured on a cold probe BEFORE any warmup) and the batcher's
bucket-cache ``bucket_cold``/``bucket_warm`` hit counts — run twice
over one persistent compilation cache
(``paddle_tpu.compile_cache_dir()``, which the servers' ``start()``
turns on) to see it turn the cold number warm across process
restarts.  The
fixed/overload modes additionally bank the warm-vs-cold PAIR:
``time_to_first_batch_cold_s`` (no prewarm) next to
``time_to_first_batch_warm_s`` (a second server with
ServingConfig(prewarm=True) — the full bucket set compiled/replayed
at replica start before the probe).

Observability (ISSUE 9): every mode's JSON line embeds a ``metrics``
object — the process metrics-registry snapshot
(``observability.metrics.registry().snapshot()``: admission outcomes,
batcher occupancy, replica pool, decode, executor step/compile
instruments; histograms summarized to count/sum/p50/p95/p99 so the
single-line contract stays bounded).  ci.sh step 5b gates that the
field parses and carries the admission instrument.

SLO verdicts (ISSUE 10): every mode's JSON line also embeds ``slo`` —
per-objective ``{attained, target, burn_rate, firing}`` from an
``observability.slo.SLOMonitor`` evaluated over the run (availability
+ p99-vs-deadline for the request modes, + decode inter-token for
--mode decode; window = the run length so a short run's burn rates
are meaningful).  Under --mode overload2x the availability objective
burns hard (sheds count against the budget) — the alert the SLO
engine exists to fire.  ci.sh 5b gates that the availability
objective is present.

Replayable: the arrival schedule is fully determined by --seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def build_model(dirname, in_dim=8, hidden=16, depth=1):
    """Save a tiny fc inference model; returns the model dir.  Larger
    in_dim/hidden/depth make each batch compute-bound — the overload
    acceptance leg uses that so the (single-thread) generator is never
    the bottleneck being measured."""
    import numpy as np  # noqa: F401

    import paddle_tpu as fluid
    from paddle_tpu import layers

    x = layers.data("x", shape=[in_dim], dtype="float32")
    h = x
    for _ in range(int(depth)):
        h = layers.fc(h, size=hidden, act="relu")
    pred = layers.fc(h, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    mdir = os.path.join(dirname, "model")
    fluid.io.save_inference_model(mdir, ["x"], [pred], exe)
    return mdir


def make_server(model_dir, replicas=1, max_batch=8, deadline_ms=250.0,
                capacity=None, max_wait_ms=2.0, warmup=True, **cfg_kw):
    """Build + start an InferenceServer over `model_dir`; pre-warms
    every (replica, bucket) compile-cache entry so the measured run
    never pays a compile."""
    import numpy as np

    from paddle_tpu import inference, serving

    def factory(i):
        return inference.create_predictor(inference.Config(model_dir))

    cfg = serving.ServingConfig(
        n_replicas=replicas, max_batch=max_batch,
        max_wait_s=max_wait_ms / 1000.0,
        default_deadline_s=deadline_ms / 1000.0,
        queue_capacity=capacity, **cfg_kw)
    srv = serving.InferenceServer(factory, cfg).start()
    if warmup:
        warm_server(srv)
    return srv


def warm_server(srv):
    """Compile every (replica, bucket) entry (the pre-measurement
    warmup make_server(warmup=True) runs)."""
    import numpy as np

    specs = srv.pool.replicas[0].predictor.feed_specs()
    for rep in srv.pool.replicas:
        for b in srv.config.buckets:
            feeds = [np.zeros((b,) + tuple(d for d in shape[1:]),
                              dtype=dtype)
                     for shape, dtype in specs.values()]
            rep.predictor.run(feeds)


def probe_first_batch(srv, deadline_s=60.0):
    """Cold-start metric (ROADMAP item 5): wall seconds from now (the
    server is up, NOTHING compiled yet) to the first completed
    request — dominated by the first bucket compile unless the
    persistent compilation cache (paddle_tpu.compile_cache_dir())
    served it from disk."""
    import numpy as np

    t0 = time.monotonic()
    srv.infer({"x": np.zeros((1, _in_dim(srv)), np.float32)},
              deadline_s=deadline_s, timeout=deadline_s)
    return time.monotonic() - t0


def _in_dim(srv):
    (shape, _), = srv.pool.replicas[0].predictor.feed_specs().values()
    return int(shape[-1])


def measure_capacity(srv, seconds=1.0, concurrency=None):
    """Closed-loop saturation throughput (req/s): `concurrency`
    threads looping submit+result as fast as replies come back."""
    import numpy as np

    from paddle_tpu import serving

    concurrency = concurrency or srv.config.max_batch
    stop_t = time.monotonic() + float(seconds)
    counts = [0] * concurrency
    in_dim = _in_dim(srv)

    def worker(k):
        rng = np.random.RandomState(1000 + k)
        x = rng.rand(1, in_dim).astype(np.float32)
        while time.monotonic() < stop_t:
            try:
                srv.infer({"x": x}, timeout=10.0)
                counts[k] += 1
            except serving.ServingError:
                pass

    t0 = time.monotonic()
    ths = [threading.Thread(target=worker, args=(k,))
           for k in range(concurrency)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    wall = time.monotonic() - t0
    return sum(counts) / wall if wall > 0 else 0.0


def run_open_loop(srv, qps, seconds, seed=0, deadline_s=None,
                  tenants=None):
    """Seeded Poisson arrivals at `qps` for `seconds`; returns the
    outcome/latency record (dict).  Every submitted request ends in
    exactly one bucket: ok / a typed rejection code / (never) silent.

    tenants (ISSUE 13): {name: fraction} traffic mix — each arrival
    draws its tenant from the seeded stream and the record grows a
    per-tenant ``tenants`` block (submitted / ok / quota_shed / shed /
    p50/p99 / goodput) next to the aggregate row, so one JSON line
    shows which tenant the admission quotas protected and which one
    they shed."""
    import numpy as np

    from paddle_tpu import serving

    rng = np.random.RandomState(int(seed))
    x = rng.rand(1, _in_dim(srv)).astype(np.float32)
    names, probs = None, None
    if tenants:
        names = sorted(tenants)
        total = sum(float(tenants[n]) for n in names)
        probs = [float(tenants[n]) / total for n in names]
    inflight = []          # (Request, tenant) futures (admitted)
    outcomes = {"ok": 0}   # code -> count (submit-time rejections too)
    per_tenant: dict = {n: {"submitted": 0, "ok": 0, "quota_shed": 0,
                            "shed": 0, "expired": 0, "other": 0,
                            "lat_ms": []}
                        for n in (names or ())}
    t0 = time.monotonic()
    next_t = t0
    n_submitted = 0
    while True:
        now = time.monotonic()
        if now - t0 >= seconds:
            break
        if now < next_t:
            time.sleep(min(next_t - now, 0.002))
            continue
        next_t += rng.exponential(1.0 / qps)
        n_submitted += 1
        tenant = None
        if names:
            tenant = names[int(rng.choice(len(names), p=probs))]
            per_tenant[tenant]["submitted"] += 1
        try:
            inflight.append((srv.submit({"x": x},
                                        deadline_s=deadline_s,
                                        tenant=tenant), tenant))
        except serving.ServingError as e:
            outcomes[e.code] = outcomes.get(e.code, 0) + 1
            if tenant is not None:
                key = {"quota": "quota_shed",
                       "overloaded": "shed",
                       "expired": "expired"}.get(e.code, "other")
                per_tenant[tenant][key] += 1
    wall = time.monotonic() - t0
    latencies = []
    for req, tenant in inflight:
        try:
            req.result(timeout=(deadline_s or
                                srv.config.default_deadline_s) + 5.0)
            outcomes["ok"] += 1
            latencies.append(req.latency_s())
            if tenant is not None:
                per_tenant[tenant]["ok"] += 1
                if req.latency_s() is not None:
                    per_tenant[tenant]["lat_ms"].append(
                        1000.0 * req.latency_s())
        except serving.ServingError as e:
            outcomes[e.code] = outcomes.get(e.code, 0) + 1
            if tenant is not None:
                key = {"quota": "quota_shed",
                       "overloaded": "shed",
                       "expired": "expired"}.get(e.code, "other")
                per_tenant[tenant][key] += 1
            if req.latency_s() is not None:
                latencies.append(req.latency_s())
    lat_ms = sorted(1000.0 * v for v in latencies if v is not None)

    def pct(p, arr=None):
        arr = lat_ms if arr is None else arr
        if not arr:
            return None
        return arr[min(len(arr) - 1, int(p / 100.0 * len(arr)))]

    tenant_rows = None
    if names:
        tenant_rows = {}
        for n in names:
            row = per_tenant[n]
            tl = sorted(row.pop("lat_ms"))
            row["share"] = float(tenants[n])
            row["goodput_qps"] = round(row["ok"] / wall, 1) \
                if wall else 0.0
            row["goodput_frac"] = round(
                row["ok"] / row["submitted"], 4) \
                if row["submitted"] else None
            row["p50_ms"] = round(pct(50, tl), 2) if tl else None
            row["p99_ms"] = round(pct(99, tl), 2) if tl else None
            tenant_rows[n] = row
    st = srv.stats()
    return {
        "offered_qps": round(n_submitted / wall, 1) if wall else 0.0,
        "goodput_qps": round(outcomes["ok"] / wall, 1) if wall else 0.0,
        "submitted": n_submitted,
        "admitted": len(inflight),
        "ok": outcomes["ok"],
        "shed": outcomes.get("overloaded", 0),
        "quota_shed": outcomes.get("quota", 0),
        "expired": outcomes.get("expired", 0),
        "failed": outcomes.get("failed", 0),
        "shutdown": outcomes.get("shutdown", 0),
        "p50_ms": round(pct(50), 2) if lat_ms else None,
        "p99_ms": round(pct(99), 2) if lat_ms else None,
        "failed_over": st["pool"]["requeues"],
        "accounted": st["accounted"],
        "tenants": tenant_rows,
        "wall_s": round(wall, 2),
    }


def run_decode_open_loop(srv, qps, seconds, seed=0, deadline_s=None,
                         mean_prompt=12, max_new=16,
                         prefix_shared=0):
    """Seeded Poisson arrivals of RAGGED decode requests (geometric
    prompt-length distribution, mean ``mean_prompt``) for ``seconds``;
    returns the outcome/latency/token-goodput record.

    prefix_shared > 0 (ISSUE 11b): every prompt carries the SAME
    seeded ``prefix_shared``-token system prompt ahead of its ragged
    tail — with the server's kv_share on, N streams amortize that
    prefill to one page set (the row banks peak shared pages next to
    tokens/s)."""
    import numpy as np

    from paddle_tpu import serving

    rng = np.random.RandomState(int(seed))
    vocab = srv.replicas[0].model.vocab
    shared = rng.randint(2, vocab, size=int(prefix_shared)) \
        if prefix_shared else None
    max_prompt = max(1, srv.config.page_size *
                     (srv.config.num_pages // 2) - max_new)
    inflight, outcomes = [], {"ok": 0}
    tokens_ok = 0
    t0 = time.monotonic()
    next_t = t0
    n_submitted = 0
    while True:
        now = time.monotonic()
        if now - t0 >= seconds:
            break
        if now < next_t:
            time.sleep(min(next_t - now, 0.002))
            continue
        next_t += rng.exponential(1.0 / qps)
        n_submitted += 1
        plen = min(int(rng.geometric(1.0 / mean_prompt)), max_prompt)
        prompt = rng.randint(2, vocab, size=max(1, plen))
        if shared is not None:
            prompt = np.concatenate([shared, prompt])[:max_prompt]
        try:
            inflight.append(srv.submit(prompt, max_new_tokens=max_new,
                                       deadline_s=deadline_s))
        except (serving.ServingError, ValueError) as e:
            code = getattr(e, "code", "invalid")
            outcomes[code] = outcomes.get(code, 0) + 1
    wall = time.monotonic() - t0
    latencies = []
    wait = (deadline_s or srv.config.default_deadline_s) + 10.0
    for req in inflight:
        try:
            out, = req.result(timeout=wait)
            outcomes["ok"] += 1
            tokens_ok += len(out)
            latencies.append(req.latency_s())
        except serving.ServingError as e:
            outcomes[e.code] = outcomes.get(e.code, 0) + 1
            if req.latency_s() is not None:
                latencies.append(req.latency_s())
    lat_ms = sorted(1000.0 * v for v in latencies if v is not None)

    def pct(p):
        if not lat_ms:
            return None
        return lat_ms[min(len(lat_ms) - 1,
                          int(p / 100.0 * len(lat_ms)))]

    st = srv.stats()
    it_p50, it_p99 = st["inter_token_p50_ms"], st["inter_token_p99_ms"]
    pages_ok, pages_detail = srv.page_accounting()
    peak_shared = max(rep_st["cache"].get("peak_shared_pages", 0)
                      for rep_st in st["replicas"].values())
    # disaggregated-tier evidence (ISSUE 14): handoff outcome counts
    # + latency percentiles from the registry histogram + the
    # in-transit page count (must be 0 at rest — part of the
    # zero-leak verdict ci.sh 5g gates)
    dis = st.get("disagg")
    handoff = None
    if dis is not None:
        from paddle_tpu.observability import metrics as obs_metrics

        snap = obs_metrics.registry().snapshot().get(
            "paddle_tpu_disagg_handoff_seconds", {})
        series = (snap.get("series") or [{}])[0]
        handoff = {
            "offered": dis["handoffs_offered"],
            "adopted": dis["handoffs_adopted"],
            "lost": dis["handoffs_lost"],
            "expired": dis["handoffs_expired"],
            "prefill_kills": dis["prefill_kills"],
            "prefill_replicas": len(dis["prefill_replicas"]),
            "in_transit_pages": dis["in_transit_pages"],
            "p50_ms": None if series.get("p50") is None
            else round(1e3 * series["p50"], 3),
            "p99_ms": None if series.get("p99") is None
            else round(1e3 * series["p99"], 3),
        }
    return {
        # decode act II (ISSUE 11): the one-JSON-line contract grows
        # acceptance-rate / sharing / chunking evidence (5b-gated)
        "spec_k": srv.config.spec_k,
        "acceptance_rate": st["spec_acceptance_rate"],
        "disagg_prefill": bool(srv.config.disagg_prefill),
        "handoff": handoff,
        "prefix_shared": int(prefix_shared),
        "peak_shared_pages": int(peak_shared),
        "prefill_chunk": srv.config.prefill_chunk,
        "prefill_chunks": st["decode"]["prefill_chunks"],
        "offered_qps": round(n_submitted / wall, 1) if wall else 0.0,
        "goodput_qps": round(outcomes["ok"] / wall, 1) if wall
        else 0.0,
        "tokens_per_sec": round(tokens_ok / wall, 1) if wall else 0.0,
        "tokens_ok": tokens_ok,
        "inter_token_p50_ms": round(it_p50, 3) if it_p50 else None,
        "inter_token_p99_ms": round(it_p99, 3) if it_p99 else None,
        "submitted": n_submitted,
        "admitted": len(inflight),
        "ok": outcomes["ok"],
        "shed": outcomes.get("overloaded", 0),
        "expired": outcomes.get("expired", 0),
        "failed": outcomes.get("failed", 0),
        "shutdown": outcomes.get("shutdown", 0),
        "p50_ms": round(pct(50), 2) if lat_ms else None,
        "p99_ms": round(pct(99), 2) if lat_ms else None,
        "failed_over": st["decode"]["failovers"],
        "preemptions": st["decode"]["preemptions"],
        "accounted": st["accounted"],
        "pages_accounted": pages_ok and not pages_detail,
        "mean_prompt": mean_prompt,
        "max_new": max_new,
        "wall_s": round(wall, 2),
    }


def parse_tenants(text):
    """'a:0.7,b:0.3' -> {'a': 0.7, 'b': 0.3} (fractions renormalized
    downstream)."""
    if not text:
        return None
    out = {}
    for part in text.split(","):
        name, _, frac = part.partition(":")
        if not name or not frac:
            raise ValueError(
                f"--tenants entry {part!r} is not name:fraction")
        out[name.strip()] = float(frac)
    return out


def parse_quotas(text):
    """'b=8,a=20qps' -> {'b': TenantQuota(max_outstanding=8),
    'a': TenantQuota(qps=20)}.  A bare integer caps outstanding; an
    ``Nqps`` suffix caps sustained admission rate (token bucket)."""
    if not text:
        return None
    from paddle_tpu.serving import TenantQuota

    out = {}
    for part in text.split(","):
        name, _, val = part.partition("=")
        if not name or not val:
            raise ValueError(f"--quota entry {part!r} is not name=N")
        val = val.strip().lower()
        if val.endswith("qps"):
            out[name.strip()] = TenantQuota(qps=float(val[:-3]))
        else:
            out[name.strip()] = TenantQuota(
                max_outstanding=int(val))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="seeded open-loop serving load generator")
    ap.add_argument("--qps", type=float, default=200.0)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--deadline-ms", type=float, default=250.0)
    ap.add_argument("--capacity", type=int, default=None,
                    help="admission queue capacity (default 4x batch)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--mode",
                    choices=["fixed", "overload2x", "decode"],
                    default="fixed")
    ap.add_argument("--capacity-seconds", type=float, default=1.0,
                    help="closed-loop capacity probe length "
                         "(overload2x)")
    ap.add_argument("--in-dim", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--depth", type=int, default=1)
    ap.add_argument("--mean-prompt", type=int, default=12,
                    help="decode mode: mean of the seeded geometric "
                         "prompt-length distribution")
    ap.add_argument("--max-new", type=int, default=16,
                    help="decode mode: max generated tokens per "
                         "request")
    ap.add_argument("--prefix-shared", type=int, default=0,
                    help="decode mode (ISSUE 11b): every prompt "
                         "carries this seeded common system-prompt "
                         "prefix and the server runs kv_share — the "
                         "row banks peak shared pages next to "
                         "tokens/s")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="decode mode (ISSUE 11c): lossless "
                         "speculative decoding with k draft proposals "
                         "per iteration — the row banks "
                         "acceptance_rate next to tokens/s")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="decode mode (ISSUE 11a): prompts longer "
                         "than this prefill in fixed chunks "
                         "interleaved with decode iterations")
    ap.add_argument("--disagg-prefill", type=int, default=0,
                    help="decode mode (ISSUE 14): run N disaggregated "
                         "prefill-tier replicas next to the decode "
                         "tier — prompt prefill hands off to decode "
                         "as a page-list transfer; the JSON line "
                         "grows handoff counts/latency and the "
                         "in-transit zero-leak verdict")
    ap.add_argument("--tenants", type=str, default=None,
                    help="ISSUE 13: per-tenant traffic mix "
                         "'a:0.7,b:0.3' — the JSON line grows "
                         "per-tenant goodput/shed/p99 rows")
    ap.add_argument("--quota", type=str, default=None,
                    help="ISSUE 13: per-tenant admission quotas "
                         "'b=8' (max outstanding) or 'a=20qps' "
                         "(token-bucket rate); over-quota submits "
                         "shed with typed QuotaExceededError")
    args = ap.parse_args(argv)
    tenants = parse_tenants(args.tenants)
    quotas = parse_quotas(args.quota)

    import jax

    jax.config.update("jax_platforms", "cpu")

    from paddle_tpu.observability import slo as obs_slo

    def make_monitor(decode=False):
        """The run's SLO set, windowed to the run length (module
        docstring); installed process-wide so /sloz shows the same
        verdicts the JSON line embeds."""
        window = max(2.0, float(args.seconds))
        slos = [obs_slo.serving_availability(objective=0.99,
                                             window_s=window,
                                             fast_fraction=0.25),
                obs_slo.serving_latency(
                    deadline_s=args.deadline_ms / 1000.0,
                    objective=0.99, window_s=window,
                    fast_fraction=0.25)]
        if decode:
            slos.append(obs_slo.decode_inter_token(
                threshold_s=max(0.05, args.deadline_ms / 1000.0),
                objective=0.99, window_s=window, fast_fraction=0.25))
        return obs_slo.install(
            obs_slo.SLOMonitor(slos=slos)).start(interval_s=0.05)

    if args.mode == "decode":
        from paddle_tpu import serving

        monitor = make_monitor(decode=True)
        # pool sized up for the shared prefix + the spec-window margin
        extra_pages = -(-(args.prefix_shared + args.spec_k + 1) // 16)
        srv = serving.DecodeServer(config=serving.DecodeConfig(
            max_batch=args.max_batch, n_replicas=args.replicas,
            max_new_tokens=args.max_new, page_size=16,
            num_pages=16 * args.max_batch +
            args.max_batch * extra_pages,
            default_deadline_s=args.deadline_ms / 1000.0,
            queue_capacity=args.capacity,
            kv_share=bool(args.prefix_shared) or None,
            spec_k=args.spec_k,
            prefill_chunk=args.prefill_chunk,
            disagg_prefill=bool(args.disagg_prefill) or None,
            n_prefill_replicas=max(1, args.disagg_prefill))).start()
        try:
            # cold first-token probe (1-token request, nothing
            # compiled yet): the decode-side time_to_first_batch_s
            t0 = time.monotonic()
            srv.decode([2, 3, 4], max_new_tokens=1,
                       deadline_s=60.0, timeout=60.0)
            ttfb = time.monotonic() - t0
            rec = run_decode_open_loop(
                srv, args.qps, args.seconds, seed=args.seed,
                deadline_s=args.deadline_ms / 1000.0,
                mean_prompt=args.mean_prompt, max_new=args.max_new,
                prefix_shared=args.prefix_shared)
        finally:
            srv.stop()
        from paddle_tpu.observability import metrics as obs_metrics

        slo_verdict = monitor.verdict()
        monitor.stop()
        rec.update({
            "metric": "decode_tokens_per_sec",
            "value": rec["tokens_per_sec"],
            "unit": "tok/s",
            "metrics": obs_metrics.registry().snapshot(),
            "slo": slo_verdict,
            "time_to_first_batch_s": round(ttfb, 3),
            "time_to_first_batch_cold_s": round(ttfb, 3),
            "time_to_first_batch_warm_s": None,
            "bucket_cold": None, "bucket_warm": None,
            "deadline_ms": args.deadline_ms,
            "replicas": args.replicas,
            "max_batch": args.max_batch,
            "seed": args.seed,
            "mode": args.mode,
        })
        print(json.dumps(rec))
        return 0

    with tempfile.TemporaryDirectory() as d:
        mdir = build_model(d, in_dim=args.in_dim, hidden=args.hidden,
                           depth=args.depth)
        monitor = make_monitor()
        srv = make_server(mdir, replicas=args.replicas,
                          max_batch=args.max_batch,
                          deadline_ms=args.deadline_ms,
                          capacity=args.capacity, warmup=False,
                          prewarm=False, quotas=quotas)
        try:
            # cold-start metric FIRST (nothing compiled yet,
            # prewarm=False so the env can't warm it behind our
            # back), then the usual full warmup so the measured run
            # never pays a compile — on a second run over the same
            # persistent cache, this number is the warm-disk replay
            # of the bucket compile
            ttfb = probe_first_batch(srv)
            warm_server(srv)
            cap_qps = None
            qps = args.qps
            if args.mode == "overload2x":
                cap_qps = measure_capacity(
                    srv, seconds=args.capacity_seconds)
                qps = 2.0 * cap_qps
                print(f"# capacity {cap_qps:.1f} req/s -> offering "
                      f"{qps:.1f}", file=sys.stderr)
            rec = run_open_loop(srv, qps, args.seconds,
                                seed=args.seed,
                                deadline_s=args.deadline_ms / 1000.0,
                                tenants=tenants)
            # SLO verdict AT RUN END — the warm-probe server below
            # must not dilute the windows the run just burned
            slo_verdict = monitor.verdict()
            monitor.stop()
            bstats = srv.stats()["batcher"]
        finally:
            srv.stop()
        # the WARM half of the cold-start pair (ROADMAP item 5): a
        # SECOND server over the same model with prewarm=True — every
        # (replica, bucket) entry compiled (or replayed from the
        # persistent compile cache) at replica start — then the
        # same first-request probe.  warm << cold is the banked
        # evidence that replica start absorbs the bucket compiles.
        srv2 = make_server(mdir, replicas=args.replicas,
                           max_batch=args.max_batch,
                           deadline_ms=args.deadline_ms,
                           capacity=args.capacity, warmup=False,
                           prewarm=True)
        try:
            ttfb_warm = probe_first_batch(srv2)
        finally:
            srv2.stop()
    from paddle_tpu.observability import metrics as obs_metrics

    rec.update({
        "metric": "serving_goodput",
        "value": rec["goodput_qps"],
        "unit": "req/s",
        "metrics": obs_metrics.registry().snapshot(),
        "slo": slo_verdict,
        "capacity_qps": round(cap_qps, 1) if cap_qps else None,
        "time_to_first_batch_s": round(ttfb, 3),
        "time_to_first_batch_cold_s": round(ttfb, 3),
        "time_to_first_batch_warm_s": round(ttfb_warm, 3),
        "bucket_cold": bstats.get("bucket_cold"),
        "bucket_warm": bstats.get("bucket_warm"),
        "deadline_ms": args.deadline_ms,
        "replicas": args.replicas,
        "max_batch": args.max_batch,
        "quota": args.quota,
        "seed": args.seed,
        "mode": args.mode,
    })
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
