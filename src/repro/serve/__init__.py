"""Concurrent serving front-end over `repro.api.LearnedIndex`
(DESIGN.md section 15): request batching/coalescing, admission control,
adaptive batch sizing, open-loop load generation, and the session
table.
"""

from .batcher import (AdaptiveBatchSizer, RejectedError, Request,
                      RequestBatcher, SERVE_OPS, ServeConfig, coalesce,
                      compatible, pow2_bucket)
from .frontend import ServeClient, ServeFrontend
from .loadgen import LoadReport, open_loop, saturation_search
from .sessions import SessionTable

__all__ = [
    "AdaptiveBatchSizer", "RejectedError", "Request", "RequestBatcher",
    "SERVE_OPS", "ServeConfig", "coalesce", "compatible", "pow2_bucket",
    "ServeClient", "ServeFrontend",
    "LoadReport", "open_loop", "saturation_search",
    "SessionTable",
]
