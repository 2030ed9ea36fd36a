"""Pallas TPU kernels for the two-phase search hot path.

Every kernel package follows the same layout -- ``kernel.py`` (the padded
``pallas_call`` + kernel body), ``ops.py`` (the public wrapper: compiled on
TPU, ``interpret=True`` on CPU for small problems, an XLA-fused jnp or
streaming-scan fallback for large ones), ``ref.py`` (the pure-jnp oracle
the parity suite pins against).  Inventory:

* ``code_match``  -- phase-1 scoring tile: ``out[q, d] = sum_c w[q, c] *
  (qcodes[q, c] == doc_codes[d, c])``, the paper's inverted-index score as
  a masked quantized-Hamming similarity.  VPU work, memory-bound; the
  ``codes_pallas`` engine dispatches here.  Emits the full (Q, d) score
  matrix (block-chunked C reduction, so parity is approximate at 1e-5).
* ``rerank_topk`` -- phase-2 exact cosine re-rank of a candidate page via
  MXU matmul tiles; final scores always come from the canonical
  ``(Q, k, n)`` einsum in :mod:`repro.core.rerank` (the last-ulp parity
  contract shared with the sharded merge).
* ``bucketize``   -- fused normalize + quantize encode used at
  build/ingest: one HBM pass instead of normalize -> rounds -> casts.
* ``fused_phase1`` -- THE query hot path (ROADMAP fused-path item):
  phase-1 scoring and the running top-``page`` selection in ONE kernel,
  folded into an accumulator held in a resident output block.  fp32
  weighted code equality (``fused`` engine): grid (d/BD, Q/BQ) with the
  query axis minor, each doc tile widened and transposed once per batch
  and summed per query by a depth-first pairwise tree over 8-row vregs.
  int8 quantized dot + per-row affine correction (``fused_int8``
  engine, table from :mod:`repro.core.quantize`): grid (Q/BQ, d/BD)
  with the doc axis minor, one MXU dot per tile.  Stable top-k makes
  the streamed fold bit-equivalent to one global top-k, and the fp32
  tree adds the reference's pairs in its order, so the fp32 path is
  BIT-identical to the composed reference while never materializing the
  (Q, d) score matrix in HBM (the composed path writes + re-reads it --
  2*Q*d*4 bytes that dominate at scale; see BENCH_kernel_scale.json).

Why the final rescore stays fp32 and unsharded: quantization and fusion
only pick WHICH candidates reach phase 2 -- reported scores always come
from the exact (Q, k, n) einsum with unsharded operands on the
coordinating device.  That keeps recall the only quality variable (the
paper's knob), and keeps every mesh shape / engine / quantization setting
bit-identical in reported scores for the hits they agree on.
"""
