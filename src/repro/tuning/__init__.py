"""Roofline-guided tile autotuning (`tile=None` == "pick for me").

See `repro.tuning.autotune` for the model/measure/cache machinery and
`repro.kernels.dispatch.resolve_plan` for how the hot paths consume it.
"""

from repro.tuning.autotune import (  # noqa: F401
    DEFAULT_TILE,
    MAX_TILE,
    MIN_TILE,
    OPS,
    Plan,
    cache_path,
    cached_executable,
    candidate_tiles,
    clear_cache,
    last_plans,
    measured,
    measuring,
    model_seconds,
    plan_for,
    set_measure,
    shape_key,
)
