"""Operations a model's mathematics needs, computed from the sizes in its
configuration file and nothing else. Recomputed work (remat) and work XLA
adds (layout copies, the optimizer's elementwise passes) do not count: this
is the numerator of model-FLOP utilisation, not the compiler's count.

    MFU = train_tokens_per_s * train_flops_per_token(cfg, seq) / peak

Copied in substance from bench.bench_ernie_train (bench.py:273-279), with
the parameter count taken from the configuration instead of the live model
(so the two embedding tables that bench.py's `len(shape) == 2` filter lets
in, 0.4% of the total, are out).
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peak(device_kind: str) -> dict:
    """The published peaks of `device_kind`; an unknown kind raises."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no published peak for device kind {device_kind!r} "
                       f"in benchmarks/peaks.json (have {sorted(table)})")
    return table[device_kind]


def encoder_matmul_params(cfg: dict) -> int:
    """Weights that sit in a matmul once per token: the blocks (qkv, out,
    two FFN matrices) — embeddings are look-ups and are not counted."""
    h, f, n = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    return n * (3 * h * h + h * h + 2 * h * f)


def train_flops_per_token(cfg: dict, seq: int, head: str = "mlm") -> float:
    """Forward + backward matmul FLOPs per trained token (PaLM appendix B
    form): 6 per matmul weight, plus attention's two batched products
    (12 * layers * seq * hidden), plus the head. `head="mlm"`: the
    hidden x hidden transform and the weight-tied hidden x vocab
    projection over every position (ErnieForPretraining); the pooler and
    NSP head run once a sequence and add hidden^2/seq per token."""
    h, n, v = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    per_tok = encoder_matmul_params(cfg)
    if head == "mlm":
        per_tok += h * h + h * v + (h * h + 2 * h) / seq
    elif head == "lm":
        per_tok += h * v
    else:
        raise ValueError(f"unknown head {head!r}")
    return 6.0 * per_tok + 12.0 * n * seq * h
