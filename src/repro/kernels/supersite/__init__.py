"""Inter-layer super-site fusion: one Pallas launch per conv chain.

The paper's headline TMP dataflow is intra- AND inter-layer fusion;
this package is the inter-layer half (ROADMAP item 2): consecutive
fusible conv sites of one stage (``core.program.SuperSite``) run as a
single launch with member intermediates only in VMEM and member weights
packed into one resident block (``pack.py``) shared across the grid
steps of a launch.
"""
