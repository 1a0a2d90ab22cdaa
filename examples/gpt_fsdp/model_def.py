"""GPT with FSDP (ZeRO-style) sharding — BASELINE config #5.

≈ the reference's examples/deepspeed/gpt_neox zero1.yaml DeepSpeedTrial:
ZeRO stages there become PartitionSpecs here (parallel/sharding.py maps
ZeRO-1/2/3 onto fsdp specs for optimizer state / gradients / parameters;
XLA inserts the reduce-scatters and all-gathers the stages imply). The
mesh hparam picks the layout: `mesh: {fsdp: 8}` is the ZeRO-2/3 analogue,
add `tp`/`sp` for megatron/sequence parallelism — same trial code.

Data: deterministic synthetic token streams with bigram structure (each
token's successor is drawn from a per-token distribution), so the LM loss
has real signal below the uniform-entropy floor. Swap `training_data` for
a tokenized corpus loader in a connected deployment.
"""
import numpy as np
import optax

from determined_clone_tpu.models import gpt
from determined_clone_tpu.telemetry.flops import gpt_train_step_flops
from determined_clone_tpu.training import JaxTrial


def _bigram_stream(n_tokens, vocab_size, seed=0, branching=4):
    """Markov-1 token stream: each token has `branching` likely successors."""
    rng = np.random.RandomState(1234)  # transition table fixed across trials
    successors = rng.randint(0, vocab_size, size=(vocab_size, branching))
    sample = np.random.RandomState(seed)
    out = np.empty(n_tokens, np.int32)
    out[0] = sample.randint(vocab_size)
    choices = sample.randint(0, branching, size=n_tokens)
    for i in range(1, n_tokens):
        out[i] = successors[out[i - 1], choices[i]]
    return out


class GPTTrial(JaxTrial):
    def __init__(self, context):
        super().__init__(context)
        get = context.get_hparam
        self.cfg = gpt.GPTConfig(
            vocab_size=int(get("vocab_size", 50304)),
            n_layers=int(get("n_layers", 12)),
            d_model=int(get("d_model", 768)),
            n_heads=int(get("n_heads", 12)),
            d_ff=int(get("d_ff", 3072)),
            max_seq_len=int(get("seq_len", 1024)),
            remat=bool(get("remat", True)),
            attention_impl=str(get("attention_impl", "auto")),
        )
        self.seq_len = int(get("seq_len", 1024))

    def initial_params(self, rng):
        return gpt.init(rng, self.cfg)

    def optimizer(self):
        get = self.context.get_hparam
        return optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.adamw(float(get("lr", 3e-4)), b1=0.9, b2=0.95,
                        weight_decay=float(get("weight_decay", 0.1))),
        )

    def loss(self, params, batch, rng):
        return gpt.loss_fn(params, self.cfg, batch[:, :-1], batch[:, 1:],
                           mesh=self.context.mesh), {}

    def sharding_rules(self):
        return gpt.GPT_SHARDING_RULES

    def train_step_flops(self):
        # without this the trainer's MFU falls back to 6N x one token per
        # sample — low by a factor of seq_len
        return gpt_train_step_flops(self.cfg, self.global_batch_size,
                                    self.seq_len)

    def training_data(self):
        bs, T = self.global_batch_size, self.seq_len
        stream = _bigram_stream(
            int(self.context.get_hparam("n_train_tokens", 2_000_000)),
            self.cfg.vocab_size)
        n_seqs = len(stream) // (T + 1)
        seqs = stream[: n_seqs * (T + 1)].reshape(n_seqs, T + 1)
        i = 0
        while True:
            sel = np.arange(i, i + bs) % n_seqs
            yield seqs[sel]
            i += bs

    def validation_data(self):
        bs, T = self.global_batch_size, self.seq_len
        stream = _bigram_stream(bs * (T + 1), self.cfg.vocab_size, seed=9)
        return [stream.reshape(bs, T + 1)]
