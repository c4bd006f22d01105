"""Operator library: one registry backing nd.* (imperative) and sym.* (symbolic).

TPU-first re-design of ``src/operator/`` (91k LoC of C++/CUDA in the
reference): each op is a single pure-JAX function lowered by XLA to every
backend, with Pallas kernels substituting where stock lowering is weak.
"""
from .registry import (OpDef, register, get_op, list_ops, alias,
                       next_rng_key, rng_scope, set_global_seed)

# Importing these modules populates the registry.
from . import elemwise       # noqa: F401
from . import reduce         # noqa: F401
from . import shape_ops      # noqa: F401
from . import nn             # noqa: F401
from . import random_ops     # noqa: F401
from . import optim_ops      # noqa: F401
from . import linalg_ops     # noqa: F401
from . import rnn            # noqa: F401
from . import vision         # noqa: F401
from . import contrib_ops    # noqa: F401
from . import extra_ops      # noqa: F401


@register("_contrib_flash_attention", aliases=("flash_attention",))
def _flash_attention_op(q, k, v, causal=False, scale=None, q_offset=0,
                        k_offset=0, block_q=None, block_k=None):
    """Pallas flash attention (see ops/pallas_attention.py). Lazy import:
    pallas/mosaic cost ~2s, which `import mxtpu` must not pay."""
    from .pallas_attention import flash_attention
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           q_offset=q_offset, k_offset=k_offset,
                           block_q=block_q, block_k=block_k)
