"""The MCCM socket service (docs/serving.md): ``EvalServer`` answers
NDJSON evaluation requests over TCP, ``ServeClient`` sends them."""
from __future__ import annotations

from .client import ServeClient
from .server import EvalServer, jsonify, summarize_search

__all__ = ["EvalServer", "jsonify", "summarize_search", "ServeClient"]
