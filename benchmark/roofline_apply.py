"""The bytes an apply of ASGD's updater needs.

By ``roofline.py``'s convention: what the *algorithm* needs, from shapes
alone.  One dispatch of the updater reads the model once, reads each
gradient it folds once, and writes the new model once, whatever the
dispatch's arity: the fold's padding slots (one cached zero vector read
again for every slot a short drain leaves) are the program's own cost and
lower the share.
"""

from __future__ import annotations


def apply_bytes(model_bytes: float, gradients: float) -> float:
    """Bytes one apply dispatch needs that folds ``gradients`` results (a
    mean over a run's dispatches may be fractional) into a model of
    ``model_bytes``: ``w`` in, ``w'`` out, each ``g`` in."""
    return (2.0 + gradients) * model_bytes
