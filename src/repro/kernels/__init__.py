"""Pallas TPU kernels for the H-FA hot spots + jnp oracles.

fa2.py           baseline FlashAttention-2 (float datapath, 'FA-2')
hfa.py           hybrid float/log H-FA kernel (MXU-compatible adaptation)
hfa_datapath.py  per-element FIX16 LNS FAU (datapath-faithful validation)
decode.py        grouped flash-decode partials + log-domain ACC merge
paged_decode.py  page-table flash-decode (serving) + page scatter/gather
paged_prefill.py page-table chunked prefill + chunk scatter / page copy
paged_verify.py  page-table multi-query speculative verify
page_codec.py    pluggable page codecs (fp / int8 / log16)
bitmath.py       bit-trick exp2/log2/PWL shared helpers
ops.py           public jit'd wrappers (impl dispatch, GQA, padding)
ref.py           pure-jnp oracles
"""
