"""The chip benchmark of the MDInference serving stack.

Everything that belongs to one configuration, traffic mix, per-layer
metric or model architecture lives in a file of its own under
``configs/``, ``traffic/``, ``metrics/`` and ``chipbench/families/``; the
rest of this package holds the general parts that read them.
"""
