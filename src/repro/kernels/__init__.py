"""Pallas TPU kernels for the paper's compute hot spots.

The paper optimizes two operations: duplicate elimination (PTT insert) and
the join (PJTT index join vs the naive nested loop).  Three kernels cover
them (each with a pure-jnp oracle in ``ref.py`` and a jitted public wrapper
in ``ops.py``):

* ``hash_mix``     — fused 64-bit triple-key mixing (elementwise, VPU).
* ``bucket_dedup`` — radix-partitioned open-addressing dedup-insert: keys are
  pre-partitioned by high hash bits so each partition's table slice fits in
  VMEM; the kernel runs the probe/claim loop entirely on-chip (one HBM pass
  over keys + one over the table, vs per-probe HBM touches for a naive port).
* ``nested_join``  — the paper's *baseline* nested-loop join as a blocked
  all-pairs kernel (child block resident in VMEM, parent tiles streamed).

Kernels target TPU (BlockSpec VMEM tiling) and are validated on CPU with
``interpret=True`` against the oracles across shape/dtype sweeps.  None
of them is on the engine's path, and only ``hash_mix`` compiles for a TPU
v5e today: Mosaic refuses ``bucket_dedup``'s block shape and
``nested_join``'s ``cumsum``.
"""
