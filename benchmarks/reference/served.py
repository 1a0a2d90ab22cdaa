"""What a served program said of the sequences it produced, kept for the
reference that recomputes them. A model with discrete choices inside it (to
which experts a token goes) is ill-conditioned at a near-tie: two scores
that differ in their last bits fall either way, and the token's logits move
by far more than any rounding. A program that reports its choices (the
engine's ``RequestResult.token_records``) lets the reference take the same
ones, so that what is compared is the arithmetic and not the luck of the
ties; how far the program's choices are the reference's own is then a
number of its own, with a floor.

An adapter fills this (``adapters/glm_moe_dsa.py``), a reference looks a
sequence up by its tokens (``reference/glm_moe_dsa.py``); both import this
module by name, so they see one dictionary.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

# tokens of a served sequence (prompt + reply) -> what the program noted of
# each position it ran (all but the last): [len - 1, W] int32
TOKEN_RECORDS: Dict[Tuple[int, ...], Any] = {}
