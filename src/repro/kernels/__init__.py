# Pallas TPU kernels for the compute hot-spots this framework optimizes:
#
#   segment_sum     — grouped aggregation (the paper's BLOCK component,
#                     Fig-11 component 9 `groupby_sum`) adapted to the MXU:
#                     one-hot matmul accumulate instead of a GPU atomic-scatter.
#   hash_join       — the Lookup's slot table: built on the host (direct
#                     `key - min` addressing for dense integer keys, else
#                     fmix32 open addressing) and its traced slot functions;
#                     XLA gathers, no Pallas body (see its ref.py).
#   radix_groupby   — radix-partitioned grouped aggregation over dense key
#                     ids: partitions the id space so the one-hot accumulator
#                     stays VMEM-bounded at any group count, replacing the
#                     sort + segment-sum route.
#   flash_attention — the staggering activity of every transformer cell
#                     (causal/bidirectional GQA + sliding window), online
#                     softmax with K/V streamed HBM->VMEM block by block.
#   mamba_scan      — the staggering activity of SSM cells; chunked selective
#                     scan with the [d_inner, d_state] carry held in VMEM
#                     scratch across a sequential grid axis.
#
# Each Pallas package has kernel code (pl.pallas_call + BlockSpec), ops.py
# (jit'd public wrapper with an interpret=True CPU path) and ref.py (pure-jnp
# oracle used by the per-kernel allclose sweeps in tests/).
