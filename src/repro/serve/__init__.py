from .engine import (  # noqa: F401
    Engine,
    GenerationResult,
    bucket_requests,
    check_capacity,
    check_queue_capacity,
    check_unique_rids,
    derive_request_keys,
    sample_tokens,
)
from .paging import (  # noqa: F401
    PagePool,
    PageStats,
    check_page_capacity,
    pages_needed,
    prefix_page_hashes,
)
from .scheduler import (  # noqa: F401
    Request,
    RequestResult,
    Scheduler,
    ServeSession,
    ServeStats,
    SlotAllocator,
    StreamHandle,
    default_prefill_buckets,
    init_params,
)
