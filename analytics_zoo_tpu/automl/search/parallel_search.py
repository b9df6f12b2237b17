"""ParallelSearchEngine — distributed trial execution, the role the
reference fills with Ray Tune over a Ray cluster
(``pyzoo/zoo/automl/search/RayTuneSearchEngine.py:28``).

Trials run in spawned worker PROCESSES, each pinned to the CPU backend (a
hyperparameter sweep must not fight the training job for the TPU; the
winning config then trains on the accelerator). Configs are generated
exactly as the sequential engine does, so results are seed-compatible —
only wall-clock changes.

The trainable must be picklable (module-level function / class), the same
contract Ray Tune imposes via cloudpickle — and, as with any library that
spawns worker processes, a driving SCRIPT must guard its entry point with
``if __name__ == "__main__":`` (spawned children re-import the main module).
"""
from __future__ import annotations

import os
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context
from typing import Any, Callable, Dict, List, Optional, Sequence

from .abstract import TrialOutput
from .local_search import LocalSearchEngine, _expand_grid, _materialize


# per-worker trainable context, installed once by the pool initializer so
# the (potentially large) dataset is pickled once per WORKER, not per trial
_worker_ctx: Dict[str, Any] = {}


def _worker_init(fit_fn, model_create_fn, data, metric):
    # trials run on the CPU backend whatever the parent holds: pin it
    # before any backend starts in this worker
    import jax
    jax.config.update("jax_platforms", "cpu")
    _worker_ctx.update(fit_fn=fit_fn, model_create_fn=model_create_fn,
                       data=data, metric=metric)


def _run_one(config) -> Dict[str, Any]:
    fit_fn = _worker_ctx["fit_fn"]
    if fit_fn is not None:
        score = fit_fn(config, _worker_ctx["data"])
    else:
        model = _worker_ctx["model_create_fn"]()
        score = model.fit_eval(_worker_ctx["data"],
                               metric=_worker_ctx["metric"], **config)
    return {"config": config, "metric": float(score)}


class ParallelSearchEngine(LocalSearchEngine):
    """Drop-in for :class:`LocalSearchEngine` with process-parallel trials.

    ``num_workers`` caps concurrent trials (defaults to the host CPU count,
    at most 8 — search trials are small by construction). Bayes search stays
    sequential (each step conditions on all previous results) — the engine
    falls back with a log note rather than silently changing the algorithm.
    """

    def __init__(self, num_workers: Optional[int] = None, seed: int = 0):
        super().__init__(seed=seed)
        self.num_workers = num_workers or min(8, os.cpu_count() or 2)

    def run(self) -> List[TrialOutput]:
        if not self._compiled:
            raise RuntimeError("compile first")
        if self.recipe.search_algorithm() == "bayes":
            import logging
            logging.getLogger("analytics_zoo_tpu").info(
                "bayes search is sequential by construction; running trials "
                "in-process")
            self.trials = self._run_bayes()
            return self.trials
        points = _expand_grid(self.space)
        n_samples = max(1, self.recipe.runtime_params()["num_samples"])
        configs = [_materialize(point, self.rng)
                   for point in points for _ in range(n_samples)]
        ctx_args = (self.fit_fn, self.model_create_fn, self.data, self.metric)
        # validate picklability UP FRONT, so a genuine trial exception later
        # propagates as itself instead of being misdiagnosed
        import pickle
        try:
            pickle.dumps(ctx_args)
        except Exception as e:
            raise ValueError(
                "ParallelSearchEngine needs a picklable trainable "
                "(module-level fit_fn / model_create_fn); use "
                f"LocalSearchEngine for closures. Underlying error: {e!r}")
        with ProcessPoolExecutor(
                max_workers=min(self.num_workers, len(configs)),
                mp_context=get_context("spawn"),
                initializer=_worker_init, initargs=ctx_args) as pool:
            results = list(pool.map(_run_one, configs))
        self.trials = [TrialOutput(config=r["config"], metric=r["metric"])
                       for r in results]
        return self.trials
