"""GLM-4.7-Flash's block (``models/glm_moe_lite.py``) trained as one member
of an expert-parallel group: latent attention through the flash kernels, a
dropless expert layer that holds ``n_routed_experts`` of
``published_n_routed_experts`` experts from ``first_expert`` on, and a
multi-token prediction module in the loss.

What is new beside ``examples/gpt_fsdp``: ``loss`` returns a third value,
the expert layers' loads, and ``apply_statistics`` moves the routers'
selection bias by them after each optimizer step (no gradient trains it, so
the optimizer's weight decay is masked off it).

Data: the synthetic bigram stream of ``examples/gpt_fsdp``.
"""
import numpy as np
import optax

from determined_clone_tpu.models import glm_moe_lite
from determined_clone_tpu.training import JaxTrial

# the hyperparameters that are GLMMoeLiteConfig fields, under its names
_SIZES = (
    "vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace",
    "num_nextn_predict_layers", "num_attention_heads", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "intermediate_size", "moe_intermediate_size", "n_routed_experts",
    "published_n_routed_experts", "first_expert", "num_experts_per_tok")
_RATES = ("routed_scaling_factor", "rope_theta", "rms_norm_eps",
          "bias_update_rate", "mtp_loss_weight")


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


class GLMMoeLiteTrial(JaxTrial):
    def __init__(self, context):
        super().__init__(context)
        get = context.get_hparam
        sizes = {k: int(get(k)) for k in _SIZES if get(k) is not None}
        rates = {k: float(get(k)) for k in _RATES if get(k) is not None}
        self.seq_len = int(get("seq_len", 1024))
        self.cfg = glm_moe_lite.GLMMoeLiteConfig(
            **sizes, **rates, max_position_embeddings=self.seq_len,
            remat=bool(get("remat", True)))

    def initial_params(self, rng):
        # an embedding of unit size: at the matrices' 0.02 the attention's
        # running mean swamps it and the routing collapses (PERF.md, PR 43)
        return glm_moe_lite.init(
            rng, self.cfg,
            embedding_std=float(self.context.get_hparam("embedding_std", 1.0)))

    def optimizer(self):
        get = self.context.get_hparam
        return optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.adamw(float(get("lr", 3e-4)), b1=0.9, b2=0.95,
                        weight_decay=float(get("weight_decay", 0.1)),
                        mask=glm_moe_lite.trained_mask),
        )

    def loss(self, params, batch, rng):
        return glm_moe_lite.loss_fn(params, self.cfg, batch[:, :-1],
                                    batch[:, 1:], mesh=self.context.mesh)

    def apply_statistics(self, params, statistics):
        return glm_moe_lite.update_selection_bias(params, self.cfg,
                                                  statistics)

    def sharding_rules(self):
        return glm_moe_lite.GLM_MOE_LITE_SHARDING_RULES

    def tokens_per_sample(self):
        return self.seq_len

    def training_data(self):
        bs, T = self.global_batch_size, self.seq_len
        stream = _bigram_stream(
            int(self.context.get_hparam("n_train_tokens", 500_000)),
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
