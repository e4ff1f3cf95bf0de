"""Model zoo: generic decoder assembly covering all assigned families."""

from .common import cast_float_weights, model_dims, quantize_params  # noqa: F401
from .parallel import NO_CTX, ParallelCtx  # noqa: F401
from .transformer import (  # noqa: F401
    check_chunked_support,
    check_paged_support,
    decode_step,
    forward_seq,
    init_params,
    init_serving_params,
    layer_pattern,
    make_cache,
    reset_cache_slot,
)
