"""Pallas TPU kernels for the speculation hot spots, with jnp oracles.

Each kernel module pairs a Pallas body with a pure-jnp reference in
``ref.py`` that the interpret-mode parity tests (``citier kernels``)
check against: ``spec_verify_attn`` (the batched s-token verify
attention), ``paged_verify_attn`` (the fused variant that streams KV
through the scalar-prefetched block table — no materialized gather),
``rmsnorm``, and ``ssd_chunk``.  ``ops.py`` is the
dispatch layer (``kernel_mode``) that picks kernel vs reference.

BlockSpec index maps in this package are pure block-address arithmetic
over grid indices and scalar-prefetch refs — enforced by repro-lint's
``pallas-index-map`` rule.
"""
