"""Device time of one decode step under the scope ``kv_cache`` (inside
``attn``): the writes of the new tokens' cache rows into the paged pool
(GPT: the two scatters of K and V; the other families: their own cache
kinds' writes). Since PR 32 a GPT decode step gathers nothing here: it reads
its context through the block table inside ``paged_attn``
(``decode_paged_attn_device_ms``); the whole-table gathers remain in
``T > 1`` programs, which no decode metric reads (ROADMAP A3(2)).
"""
from benchmarks.harness import scopes

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return scopes.step_ms(ctx, "serve", "parts", "kv_cache")
