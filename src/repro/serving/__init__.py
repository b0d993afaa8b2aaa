from repro.serving.batcher import CostEvalBatcher  # noqa: F401
from repro.serving.cost_cache import (  # noqa: F401
    CostMemoCache,
    PersistentCostCache,
)
from repro.serving.engine import Engine, Request, ServeConfig  # noqa: F401
from repro.serving.http_service import (  # noqa: F401
    HttpConfig,
    QueueFull,
    SearchClient,
    SearchHTTPService,
    outcome_to_json,
    request_from_spec,
)
from repro.serving.search_service import (  # noqa: F401
    BATCHED_METHODS,
    COSTS_BATCHED_METHODS,
    RAW_BATCHED_METHODS,
    SearchCancelled,
    SearchService,
    SearchTicket,
    ServiceConfig,
)
