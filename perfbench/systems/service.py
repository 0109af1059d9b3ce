"""Deployment through ``RetrievalService`` over SQLite + FTS5 (the agent's
SQL surface), with its batched engine attached as ``launch/serve.py`` does.

The corpus goes into an in-memory SQLite database through the program's
own schema functions; the service loads its matrix from there.  Serves
``perfbench/requests/sql.py``.
"""

from __future__ import annotations

import sqlite3

from repro.serve.retrieval import RetrievalService
from repro.sqlio import schema

from perfbench.lib.corpus import Corpus

DESCRIPTION = "Agentic coding conversation history. Sessions, messages, tool calls, and output."
INSERT_BLOCK = 20_000


def load_sqlite(corpus: Corpus) -> sqlite3.Connection:
    conn = sqlite3.connect(":memory:", check_same_thread=False)
    schema.build_schema(conn, DESCRIPTION)
    schema.insert_sources(conn, corpus.session_rows())
    rows = corpus.sql_rows()
    for i in range(0, len(rows), INSERT_BLOCK):
        schema.insert_chunks(conn, rows[i:i + INSERT_BLOCK], corpus.matrix[i:i + INSERT_BLOCK])
    return conn


class System:
    def __init__(self, cfg: dict, corpus: Corpus, embed) -> None:
        self.cfg = cfg
        self.conn = load_sqlite(corpus)
        self.svc = RetrievalService(self.conn, dim=int(cfg["dim"]), embedder=embed,
                                    now=float(cfg["now"]), engine=cfg["engine"])
        self.engine = self.svc.serving(max_batch=int(cfg["max_batch"]))

    def counters(self) -> dict:
        """The service's counters; ``router_threshold`` is the Phase-1
        router's learned masked/gather crossover as the window left it."""
        stats = self.svc.stats()
        return dict(stats, router_threshold=stats["prefilter"]["threshold_effective"])

    def close(self) -> None:
        self.svc.close()
        self.conn.close()
