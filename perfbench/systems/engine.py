"""Deployment through ``VectorCache`` + ``BatchedRetrievalEngine`` (no SQL).

The served path of ``launch/serve.py``: one segment holding the whole
corpus, the registry backend the configuration names, and the engine's
own batching (``max_batch`` from the configuration, every other setting
its default).  Serves ``perfbench/requests/search.py``.
"""

from __future__ import annotations

from repro.core.backends import get_backend
from repro.core.vectorcache import VectorCache
from repro.serve.engine import BatchedRetrievalEngine

from perfbench.lib.corpus import Corpus


class System:
    def __init__(self, cfg: dict, corpus: Corpus, embed) -> None:
        self.cfg = cfg
        self.now = float(cfg["now"])
        self.cache = VectorCache(corpus.ids, corpus.matrix, corpus.timestamps, embed,
                                 normalized=True)
        self.backend = get_backend(cfg["engine"])
        self.engine = BatchedRetrievalEngine(self.cache, max_batch=int(cfg["max_batch"]),
                                             now=self.now, engine=self.backend)

    def counters(self) -> dict:
        plan = getattr(self.backend, "plan_cache", None)
        dev = getattr(self.backend, "device_cache_stats", None)
        return {"engine": self.engine.stats(),
                "plan_cache": plan.stats() if plan else None,
                "device_cache": dev() if dev else None}

    def close(self) -> None:
        self.engine.close()
