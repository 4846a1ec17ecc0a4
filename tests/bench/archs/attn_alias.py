"""bench/archs/attn.py under another mixer name: an architecture that the
benchmark's tests add by files alone."""
from bench.archs.attn import (SCOPES, block, final_norm,  # noqa: F401
                              layer_kind, matmul_params, mixer_flops,
                              spec_check)
