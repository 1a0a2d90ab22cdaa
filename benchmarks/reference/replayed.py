"""What a trained program said of the steps the reference follows, kept for
the reference: the experts every token of a checked batch took, with the
parameters that step started from, and where the program's share of the
experts starts. ``reference/served.py`` says why a model with discrete
choices inside it is compared under the program's own choices; this is the
same hand-over for training.

An adapter fills it (``adapters/glm4_moe_lite.py``), a reference looks a
batch up by its tokens (``reference/glm4_moe_lite.py``); both import this
module by name, so they see one dictionary.
"""
from __future__ import annotations

import hashlib
from typing import Any, Dict

import numpy as np

# key(batch) -> {"first_expert": int, "experts": {stack: [L, N, k] int32}}
ROUTING: Dict[str, Dict[str, Any]] = {}


def key(batch: Any) -> str:
    """A checked batch by its tokens (int32 [rows, T + 1])."""
    return hashlib.sha1(np.ascontiguousarray(
        np.asarray(batch, np.int32)).tobytes()).hexdigest()
