"""Built-in model families (≈ the reference's examples/ + model_hub coverage)."""
from determined_clone_tpu.models import (
    bert,
    evabyte,
    gpt,
    minicpm_sala,
    mlp,
    mnist_cnn,
    resnet,
    vit,
)

__all__ = ["bert", "evabyte", "gpt", "minicpm_sala", "mlp", "mnist_cnn", "resnet", "vit"]
