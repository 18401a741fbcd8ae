"""The chip benchmark of the MDInference serving stack.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under ``configs/``, ``traffic/`` and
``metrics/``; this package holds the general parts that read them.
"""
