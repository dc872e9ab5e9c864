"""The ``serve_open_loop`` runner: open-loop arrivals at one tenant of
``GALService``.

Parameters (``bench/traffic/<mix>.json``): ``rate_per_s`` (offered, fixed),
``template_seed`` (the sizes and gaps every seed shares), ``batch_share``,
``batch_rows_min`` and ``batch_rows_max`` (batch requests' rows,
log-uniform), ``max_batch`` (the registry's) and ``pool_rows`` (held-out
rows the requests draw from). The collaboration served is the
configuration's, fitted at set-up on its ``n_rows``.
"""
from __future__ import annotations

import math
import tempfile
import time
from typing import Any, Dict, List

import jax
import numpy as np

from bench.lib import harness, tabular
from bench.lib.tabular_run import TabularRun


class Runner(TabularRun):
    """Open-loop Poisson arrivals at one tenant, loaded from disk through
    ``ArtifactRegistry`` into ``GALService``. Each request is timed from
    the moment it was due to the moment its rows were on the host.

    Every seed sends the same multiset of request sizes and gaps, in an
    order and over rows that the seed draws, so that seeds change which
    requests come when and not how much work there is."""

    TENANT = "tenant0"

    def setup(self):
        from repro.checkpoint import save_artifact
        from repro.serve import ArtifactRegistry, GALService
        t = self.traffic
        self.xs, self.y, self.pool, _ = self.make_data(
            self.cfg["n_rows"], t["pool_rows"])
        result = self.fit(self.xs, self.y)
        harness.OUT_DIR.mkdir(parents=True, exist_ok=True)
        self._tmp = tempfile.TemporaryDirectory(
            prefix="artifact-", dir=harness.OUT_DIR)
        path = save_artifact(result, f"{self._tmp.name}/{self.TENANT}")
        del result
        self.registry = ArtifactRegistry(max_batch=t["max_batch"])
        self.registry.register(self.TENANT, path)
        self.service = GALService(self.registry)
        with jax.profiler.TraceAnnotation("warmup"):
            self.service.warmup(self.TENANT)
        self.pool_host = [np.asarray(x) for x in self.pool]
        # every flush size the traffic can make (1 .. max_batch rows): the
        # batcher slices each launch's output on the device, one small
        # program per row count
        with jax.profiler.TraceAnnotation("warmup"):
            for rows in range(1, t["max_batch"] + 1):
                self.service.submit(self.TENANT, [x[:rows] for x in
                                                  self.pool_host]).result(60)

    @property
    def predict(self):
        return self.registry.get(self.TENANT).predict

    def _schedule(self, seconds: float):
        """round(rate x seconds) requests whose gaps and sizes come from the
        traffic's template seed, rescaled so that every arrival falls
        inside the window; the run's seed permutes them and picks rows."""
        t = self.traffic
        n = max(1, int(round(t["rate_per_s"] * seconds)))
        tmpl = np.random.default_rng(t["template_seed"])
        gaps = tmpl.exponential(1.0 / t["rate_per_s"], n)
        gaps *= (seconds - 0.5 / t["rate_per_s"]) / gaps.sum()
        batch = tmpl.random(n) < t["batch_share"]
        lo, hi = math.log(t["batch_rows_min"]), math.log(t["batch_rows_max"])
        rows = np.where(batch, np.floor(np.exp(tmpl.uniform(
            lo, math.log(hi + 1), n))).astype(int), 1)
        rows = np.clip(rows, 1, t["batch_rows_max"])
        run = np.random.default_rng(self.seed)
        self.gaps = gaps[run.permutation(n)]
        self.rows = rows[run.permutation(n)]
        n_pool = self.pool_host[0].shape[0]
        self.starts = run.integers(0, n_pool - self.rows + 1)

    def _flushes(self) -> int:
        tenants = self.service.stats()["tenants"]
        return tenants[self.TENANT]["batches"] if self.TENANT in tenants \
            else 0

    def window(self, seconds: float) -> Dict[str, Any]:
        self._schedule(seconds)
        arrivals = np.cumsum(self.gaps)
        n = len(arrivals)
        bp = self.predict
        before = (bp.launches, bp.rows_launched, bp.rows_padded,
                  self._flushes())
        done = np.full(n, np.nan)
        late = np.zeros(n)
        futures = []
        t0 = time.perf_counter()
        due = t0 + arrivals[:n]
        for i in range(n):
            wait = due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[i] = time.perf_counter() - due[i]
            s, r = self.starts[i], self.rows[i]
            with jax.profiler.TraceAnnotation("submit"):
                fut = self.service.submit(
                    self.TENANT, [x[s:s + r] for x in self.pool_host])
            fut.add_done_callback(
                lambda _f, i=i: done.__setitem__(i, time.perf_counter()))
            futures.append(fut)
        close = t0 + seconds
        self.served, failed = [], 0
        with jax.profiler.TraceAnnotation("result"):
            for fut in futures:
                try:
                    self.served.append(np.asarray(fut.result(
                        timeout=max(close + 60.0 - time.perf_counter(),
                                    0.01))))
                except Exception:                       # noqa: BLE001
                    self.served.append(None)
                    failed += 1
        ok = ~np.isnan(done) & np.array([x is not None for x in self.served])
        lat_ms = np.where(ok, done - due, np.inf) * 1e3
        self.counters.update(
            launches=bp.launches - before[0],
            rows_launched=bp.rows_launched - before[1],
            rows_padded=bp.rows_padded - before[2],
            flushes=self._flushes() - before[3], requests=n,
            late_max_ms=float(late.max() * 1e3),
            late_p99_ms=float(np.percentile(late, 99) * 1e3))
        harness.log(f"generator lateness: p99 "
                    f"{self.counters['late_p99_ms']:.3f} ms, max "
                    f"{self.counters['late_max_ms']:.3f} ms over {n} "
                    f"requests")
        last = float(np.nanmax(done)) if ok.any() else close
        return {"attempted": n, "failed": failed, "metrics": {
            "serve_p99_ms": float(np.percentile(lat_ms, 99)),
            "serve_requests_per_s": float(ok.sum()) / (max(last, close)
                                                       - t0)}}

    def release(self):
        self.service.close()
        del self.service, self.registry
        self._tmp.cleanup()

    def _readings(self, served) -> Dict[str, float]:
        """The gaps of every served row from the reference's prediction,
        relative to the reference predictions' root mean square: their
        root mean square, and the widest (logged: the later rounds of the
        fit move every row a little, so the widest gap does not part the
        program from the control)."""
        if not hasattr(self, "_want"):
            ref = self.reference(self.xs, self.y)
            self._want = np.asarray(tabular.reference_predict(ref, self.pool),
                                    np.float64)
        want = self._want
        scale = float(np.sqrt(np.mean(want ** 2)))
        sq, widest, rows = 0.0, 0.0, 0
        for i, got in enumerate(served):
            s, r = self.starts[i], self.rows[i]
            if got is None or got.shape != (r, 1):
                return {"served_rms_gap": float("inf"),
                        "served_gap": float("inf")}
            d = got[:, 0].astype(np.float64) - want[s:s + r]
            sq += float(np.sum(d * d))
            widest = max(widest, float(np.max(np.abs(d))))
            rows += r
        return {"served_rms_gap": float(np.sqrt(sq / rows)) / scale,
                "served_gap": widest / scale}

    def check(self) -> List[dict]:
        return harness.compare(self._readings(self.served),
                               self.cfg["limits"]["serve"])

    def control(self) -> Dict[str, float]:
        """Every served row replaced by the reference's prediction computed
        in bfloat16."""
        ctl = self.reference(self.xs, self.y, "bfloat16")
        pred = np.asarray(tabular.reference_predict(ctl, self.pool))
        return self._readings([pred[s:s + r, None] for s, r in
                               zip(self.starts, self.rows)])
