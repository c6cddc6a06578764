"""A cell of the benchmark cut to a size the CPU test run can hold: the
real configuration and traffic files with every width and the sequence
shrunk, so the harness, the trainer and the reference run end to end."""

import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import spec  # noqa: E402

LIMITS = {"loss_gap": 1e-4, "grad_sqnorm_gap": 1e-4, "var_l1_gap": 1e-4,
          "first_grad_gap": 1e-4, "change_gap": 1e-4, "rung_mismatches": 0}


def tiny(cell: str) -> dict:
    r = copy.deepcopy(spec.resolve(spec.benchmark(), cell))
    r["config"]["model"].update(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=4, num_hidden_layers=2, vocab_size=256)
    r["config"]["program"]["overrides"] = dict(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=256)
    r["config"]["train"]["remat"] = "none"
    r["traffic"]["seq_len"] = 32
    rungs = r["traffic"]["rungs"]
    r["cell"] = {"step_s": {str(b): 0.05 for b in rungs},
                 "limits": dict(LIMITS)}
    return r
