"""Real-data convergence gates — the nightly accuracy bar.

≈ the reference's e2e_tests/tests/nightly/test_convergence.py:25 (mnist
best validation accuracy > 0.97). The build environment has no egress, so
the real data is sklearn's bundled handwritten-digits scans
(utils/data.py digits_dataset — genuine held-out split, same task family);
the gate value carries over unchanged.
"""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

sys.path.insert(0, str(REPO / "examples" / "mnist"))
from model_def import MnistTrial  # noqa: E402

from determined_clone_tpu import core  # noqa: E402
from determined_clone_tpu.config.experiment import ExperimentConfig  # noqa: E402
from determined_clone_tpu.training import Trainer, TrialContext  # noqa: E402


def test_digits_cnn_beats_097(tmp_path):
    """The committed mnist example config's model, through the real
    Trainer, on real scans, to the reference's 0.97 bar."""
    cfg = ExperimentConfig.from_dict({
        "name": "convergence-digits",
        "entrypoint": "model_def:MnistTrial",
        "searcher": {"name": "single", "metric": "accuracy",
                     "smaller_is_better": False,
                     "max_length": {"batches": 220}},
        "scheduling_unit": 55,
        "min_validation_period": {"batches": 55},
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(tmp_path)},
    })
    hparams = {"global_batch_size": 64, "lr": 1e-3,
               "n_filters_1": 16, "n_filters_2": 32, "dataset": "digits"}
    with core.init(config=cfg, trial_id=1) as cctx:
        ctx = TrialContext(config=cfg, hparams=hparams, core=cctx)
        backend = cctx.train._backend
        result = Trainer(MnistTrial(ctx)).fit()
        assert result["batches_trained"] == 220
        val = [r for r in backend.records if r["group"] == "validation"]
        assert val, "no validation reports"
        best = max(r["metrics"]["accuracy"] for r in val)
        print(f"\n[convergence] digits best val accuracy: {best:.4f}")
        assert best > 0.97, f"accuracy {best:.4f} below the 0.97 gate"
