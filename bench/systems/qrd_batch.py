"""Batched QR decompositions through `repro.qrd.QRDEngine`, closed loop.

One engine call per period, with one batch in flight: the caller hands a
device-resident batch of matrices to the engine and waits until Q and R
are ready on the device before it hands over the next.  The batches come
from a pool of distinct ones made on the device at set-up from the seed.

Config keys: ``engine`` (the `QRDConfig` fields), ``compute_q``,
``rx``/``tx`` (the complex channel's shape; the real-valued matrices are
2 rx x 2 tx), ``limits`` (the correctness limits).  Traffic keys:
``matrices_per_call``, ``pool``, ``warmup_calls``, ``sample_calls``.
"""
from __future__ import annotations

import time

import numpy as np

from bench.lib import mimo, seeds, spec, stats


def engine_config(engine: dict):
    from repro.core.formats import FloatFormat
    from repro.core.givens import GivensConfig
    from repro.qrd import QRDConfig
    kw = dict(engine)
    if "givens" in kw:
        g = dict(kw.pop("givens"))
        g["fmt"] = FloatFormat(*g.pop("fmt"))
        kw["givens"] = GivensConfig(**g)
    return QRDConfig(**kw)


class Driver:
    def __init__(self, cell, seed: int, seconds: float):
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.batch = int(self.traffic["matrices_per_call"])
        self.m = 2 * int(self.cfg["rx"])
        self.n = 2 * int(self.cfg["tx"])
        self.compute_q = bool(self.cfg["compute_q"])

    # -- set-up: program, inputs, every shape the window uses -----------------
    def setup(self):
        import jax
        from repro.qrd import QRDEngine
        self.jax = jax
        t0 = time.perf_counter()
        self.engine = QRDEngine(engine_config(self.cfg["engine"]))
        self.pool = jax.block_until_ready(mimo.channel_pool(
            self.seed, slots=int(self.traffic["pool"]), batch=self.batch,
            rx=int(self.cfg["rx"]), tx=int(self.cfg["tx"])))
        t1 = time.perf_counter()
        jax.block_until_ready(self.call(self.pool[0]))
        t2 = time.perf_counter()
        for i in range(1, int(self.traffic["warmup_calls"])):
            jax.block_until_ready(self.call(self.pool[i % len(self.pool)]))
        self.setup_phases = {"inputs_s": t1 - t0, "first_call_s": t2 - t1,
                             "warm_calls_s": time.perf_counter() - t2}

    def call(self, A):
        return self.engine(A, compute_q=self.compute_q)

    # -- the measured window ---------------------------------------------------
    def window(self, seconds: float, spans):
        block = self.jax.block_until_ready
        pool, P = self.pool, len(self.pool)
        K = int(self.traffic["sample_calls"])
        rng = np.random.default_rng(seeds.words(self.seed, 2))
        kept = []                       # reservoir sample of (call, pool i, out)
        lat = []
        i = 0
        t_start = time.perf_counter()
        deadline = t_start + seconds
        while True:
            a = time.perf_counter()
            with spans("engine_call"):
                out = self.call(pool[i % P])
            with spans("wait_device"):
                block(out)
            b = time.perf_counter()
            lat.append(b - a)
            if i < K:
                kept.append((i, i % P, out))
            else:
                j = int(rng.integers(0, i + 1))
                if j < K:
                    kept[j] = (i, i % P, out)
            i += 1
            if b >= deadline:
                break
        window_s = b - t_start
        self.kept = kept
        done = i * self.batch
        lat = np.asarray(lat)
        med = float(np.median(lat))
        slow = lat[lat > 2 * med]
        return {
            "diag": {"latency_ms_median": med * 1e3,
                     "latency_ms_p99": float(np.percentile(lat, 99)) * 1e3,
                     "latency_ms_max": float(lat.max()) * 1e3,
                     "calls_over_2x_median": int(slow.size),
                     "seconds_over_median_in_those": float(
                         np.sum(slow - med))},
            "window_s": window_s,
            "calls": i,
            "call_span": "engine_call",
            "attempted": done,
            "failed": 0,
            "metrics": {
                "qrd_per_s": done / window_s,
                "slot_latency_p95_ms": stats.p95(lat) * 1e3,
            },
            "work": {"kernel": self.cfg["kernel"], "m": self.m, "n": self.n,
                     "compute_q": self.compute_q,
                     "iters": self.engine.config.blockfp_iters(),
                     "matrices_per_call": self.batch},
        }

    # -- correctness: the window's own outputs against the reference ----------
    def answers(self):
        """Host copies of the sampled calls' inputs and outputs; frees the
        program's device state."""
        got = []
        for _, p, (Q, R) in sorted(self.kept, key=lambda t: t[0]):
            got.append((np.asarray(self.pool[p]),
                        None if Q is None else np.asarray(Q), np.asarray(R)))
        del self.kept, self.pool, self.engine
        return got

    def check(self, got):
        """[(name, value, limit)] — the numbers compared, each under its
        limit for a correct run."""
        ref = spec.reference(self.cfg["reference"])
        lim = self.cfg["limits"]
        r_gap = q_gap = 0.0
        for A, Q, R in got:
            Qr, Rr = ref.qr(A)
            scale = np.max(np.abs(A), axis=(-2, -1))
            if Q is not None:
                Q, R = ref.positive_diag(Q, R)
                q_gap = max(q_gap, float(np.max(np.abs(Q - Qr))))
            else:
                R = ref.positive_diag(np.zeros_like(Qr), R)[1]
            r_gap = max(r_gap, float(np.max(
                np.max(np.abs(R - Rr), axis=(-2, -1)) / scale)))
        out = [("r_gap", r_gap, lim["r_gap"])]
        if self.compute_q:
            out.append(("q_gap", q_gap, lim["q_gap"]))
        return out
