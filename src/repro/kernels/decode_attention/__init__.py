from repro.kernels.decode_attention.kernel import sanitize_block_tables
from repro.kernels.decode_attention.ops import (paged_decode_attention,
                                                paged_mla_decode)
from repro.kernels.decode_attention.ref import (paged_decode_attention_ref,
                                                paged_mla_decode_ref)

__all__ = ["paged_decode_attention", "paged_decode_attention_ref",
           "paged_mla_decode", "paged_mla_decode_ref",
           "sanitize_block_tables"]
