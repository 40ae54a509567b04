"""Open-loop request traffic through ``ServiceRouter.submit_nowait``.

Independent users each send one image at a time and do not wait for one
another: requests are due on a fixed schedule whatever the service does,
and each one's latency runs from when it was due to when its result is
on the host.  The mix fixes ``rate_per_s``, the share of each datapath
and the router's settings.  Every seed sends the same set of
inter-arrival gaps (the quantiles of an exponential distribution, i.e.
Poisson arrivals) and the same number of each datapath, in an order
drawn from the seed, so a seed changes the order of the work and not its
amount.

The mix names, for each datapath, how its payloads are made from a host
pool of images drawn from the seed (``payload``: a function of
:mod:`bench.reference_jnp` run on the device in the configuration's
accumulator, or null for the image itself) and which function of
:mod:`bench.reference` gives a request's answer from its payload
(``reference``).  A datapath is added by data alone where those
functions exist.  The configuration's ``knobs`` go into every route's
spec.

A request that is refused, fails, or is answered by the router's
degraded fallback path counts as failed; the counts are deltas of
``ServiceRouter.stats()`` over the window.
"""
from __future__ import annotations

import asyncio
import math
import time

import numpy as np

from bench import reference, reference_jnp, stats

#: how long after the window closes the generator waits for answers
DRAIN_S = 60.0
#: short names of the compared numbers, as the batch cells print them
MISMATCH = {"forward": "fwd", "inverse": "inv"}


def schedule(rate_per_s: float, seconds: float, shares: dict, rng):
    """(offsets in s, datapath of each request) for one window."""
    count = max(1, int(round(rate_per_s * seconds)))
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate_per_s
    gaps = gaps * (seconds / gaps.sum())     # the schedule spans the window
    rng.shuffle(gaps)
    offsets = np.cumsum(gaps) - gaps[0]
    kinds = []
    names = sorted(shares)
    for name in names:
        kinds += [name] * int(round(shares[name] * count))
    kinds = (kinds + [names[0]] * count)[:count]
    rng.shuffle(kinds)
    return offsets, kinds


class State:
    def __init__(self, h):
        from repro.launch.router import ServiceRouter

        cfg, mix = h.config, h.traffic
        self.mix = mix
        self.n = int(cfg["n"])
        self.dtype = np.dtype(cfg["dtype"])
        self.router = ServiceRouter(**mix["router"])
        knobs = dict(cfg.get("knobs") or {})
        self.specs = {dp: dict(knobs, n=self.n, dtype=cfg["dtype"],
                               datapath=dp) for dp in sorted(mix["shares"])}
        with h.span("bench.prefill"):
            self.router.prefill(list(self.specs.values()))
        # The router has no public handle on a route's service: this is
        # the one place the benchmark reaches for it, to run each warm
        # size once before the window and to read the batch counters
        # that ServiceRouter.stats() does not carry.
        self.services = {dp: self.router._routes[
            self.router.route_key(spec)].service
            for dp, spec in self.specs.items()}
        rng = h.rng("pool")
        hi = int(np.iinfo(self.dtype).max) + 1
        self.pool = rng.integers(0, hi, (int(mix["pool"]), self.n, self.n),
                                 dtype=self.dtype)
        self.payloads = {}

    def counters(self) -> dict:
        r = self.router.stats()
        out = {"requests": sum(int(x["requests"])
                               for x in r["routes"].values()),
               "fallback_rows": int(r["fallback_uses"])}
        out["batches"] = out["padded_slots"] = 0
        for svc in self.services.values():
            s = svc.stats()
            out["batches"] += int(s["batches"])
            out["padded_slots"] += int(s["padded_slots"])
        return out


def make_payloads(cfg: dict, mix: dict, pool: np.ndarray) -> dict:
    """Each datapath's payloads: the pool's images, or the mix's
    ``payload`` function of :mod:`bench.reference_jnp` applied to them on
    the device in the configuration's accumulator."""
    import jax

    out = {}
    for dp in sorted(mix["shares"]):
        name = (mix.get("payload") or {}).get(dp)
        if name is None:
            out[dp] = pool
            continue
        fn = getattr(reference_jnp, name)
        acc = cfg["accumulator"]
        out[dp] = np.asarray(jax.jit(lambda x: fn(x, acc))(pool))
    return out


def setup(h):
    st = State(h)
    # every warm size of every route once on the device
    for svc in st.services.values():
        for b in svc.sizes:
            zeros = np.zeros((b,) + svc.request_shape,
                             np.dtype(svc.request_dtype.name))
            svc.execute(zeros)
    with h.span("bench.payloads"):
        st.payloads = make_payloads(h.config, h.traffic, st.pool)
    return st


def run_window(h, st, seconds: float) -> dict:
    mix = h.traffic
    rng = h.rng("schedule")
    offsets, kinds = schedule(float(mix["rate_per_s"]), seconds,
                              mix["shares"], rng)
    count = len(offsets)
    picks = rng.integers(0, len(st.pool), count)
    check_idx = set(h.rng("check").choice(
        count, min(count, int(mix["check_requests"])), replace=False)
        .tolist())
    due = np.zeros(count)
    late = np.zeros(count)
    done = np.full(count, np.nan)
    answers = {}
    failed = set()
    before = st.counters()

    async def drive():
        from repro.launch.errors import ServiceError

        router = st.router
        await router.start()
        loop = asyncio.get_running_loop()
        futs = []

        def on_done(fut, i):
            if fut.cancelled() or fut.exception() is not None:
                failed.add(i)
                return
            done[i] = loop.time()
            if i in check_idx:
                answers[i] = fut.result()

        start = loop.time() + 0.005
        try:
            for i in range(count):
                due[i] = start + offsets[i]
                delay = due[i] - loop.time()
                if delay > 0:
                    await asyncio.sleep(delay)
                with h.span("bench.submit"):
                    now = loop.time()
                    late[i] = now - due[i]
                    payload = st.payloads[kinds[i]][picks[i]]
                    try:
                        fut = router.submit_nowait(st.specs[kinds[i]],
                                                   payload)
                    except ServiceError:
                        failed.add(i)
                        continue
                fut.add_done_callback(lambda f, i=i: on_done(f, i))
                futs.append(fut)
            closed = loop.time()
            if futs:
                await asyncio.wait(futs, timeout=DRAIN_S)
            return closed - start, closed, loop.time() - closed
        finally:
            await router.shutdown()

    t0 = time.perf_counter()
    window_s, closed, drain_s = asyncio.run(drive())
    elapsed = time.perf_counter() - t0
    after = st.counters()
    lat = done - due
    unanswered = np.isnan(lat)
    for i in np.flatnonzero(unanswered):
        failed.add(int(i))
    # a request with no answer is slower than any answered one
    lat_all = np.where(unanswered, math.inf, lat)
    p50 = stats.percentile(lat_all, 50)
    p95 = stats.percentile(lat_all, 95)
    cap = 1e3 * (seconds + DRAIN_S)
    metrics = {"latency_p50_ms": min(1e3 * p50, cap),
               "latency_p95_ms": min(1e3 * p95, cap)}
    delta = {k: after[k] - before[k] for k in after}
    fallback = delta["fallback_rows"]
    backlog = int(np.count_nonzero(~(done <= closed)))
    return {
        "info": {"offered_per_s": count / window_s,
                 "answered_per_s": float(np.count_nonzero(done <= closed))
                 / window_s,
                 "backlog_at_close": backlog, "drain_s": drain_s,
                 "rejected_or_failed": len(failed), "fallback_rows": fallback,
                 "mean_batch": delta["requests"] / max(1, delta["batches"])},
        "attempted": count, "failed": len(failed) + fallback,
        "elapsed_s": elapsed, "metrics": metrics,
        "counts": {"requests": delta["requests"],
                   "batches": delta["batches"],
                   "padded_slots": delta["padded_slots"],
                   "fallback_rows": fallback,
                   "client_late_s": late.tolist(),
                   "ops": {k: sum(1 for i in range(count)
                                  if kinds[i] == k and not unanswered[i])
                           for k in mix["shares"]}},
        "kinds": kinds, "picks": picks, "check_idx": sorted(check_idx),
        "answers": answers,
    }


def collect(h, st, window: dict) -> dict:
    s = st.router.stats()
    print(f"bench: router fallback_uses={s['fallback_uses']} "
          f"degraded_compiles={s['degraded_compiles']} "
          f"rejected={s['rejected']}", flush=True)
    samples = []
    for i in window["check_idx"]:
        kind = window["kinds"][i]
        samples.append({"kind": kind,
                        "x": st.payloads[kind][window["picks"][i]],
                        "out": window["answers"].get(i)})
    return {"samples": samples}


def check(h, col: dict) -> dict:
    """{name: (value, limit)}: sampled requests left unanswered, and per
    datapath the mismatched output elements against the mix's reference
    function (exact integer arithmetic: limit 0)."""
    missing = 0
    groups = {dp: [] for dp in sorted(h.traffic["shares"])}
    for it in col["samples"]:
        if it["out"] is None:
            missing += 1
        else:
            groups[it["kind"]].append(it)
    out = {"unanswered": (missing, 0)}
    for dp, items in groups.items():
        bad = 0
        if items:
            fn = getattr(reference, h.traffic["reference"][dp])
            want = fn(np.stack([it["x"] for it in items]))
            for it, w in zip(items, want):
                got = np.asarray(it["out"])
                bad += (int(w.size) if got.shape != w.shape else
                        int(np.count_nonzero(got.astype(np.int64) != w)))
        out[f"{MISMATCH.get(dp, dp)}_mismatch"] = (bad, 0)
    return out
