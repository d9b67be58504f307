"""The benchmark's FLOP and byte counts against counts made by hand."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402


def model(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())["model"]


#: The sizes of hf:bigcode/starcoder2-7b (GQA, GELU MLP), 3 of its 32
#: layers: a second shape for the count, ahead of its cell.
STARCODER = {"num_layers": 3, "d_model": 4608, "n_heads": 36,
             "n_kv_heads": 4, "d_ff": 18432, "vocab_size": 49152,
             "mlp_kind": "gelu"}


# qwen1.5-4b, 4 layers: q, k, v, o are 2560 x 2560 each (20 heads of 128,
# 20 KV heads) = 4 x 6,553,600; SwiGLU 3 x 2560 x 6912 = 53,084,160; one
# layer 79,298,560, four 317,194,240; head 2560 x 151936 = 388,956,160.
QWEN_MATMUL = 706_150_400
# 6 x 706,150,400 + 3 passes x 4 layers x 2 x 2560 x 2049 (causal: 2 FLOPs
# x 2 products x H*Dh x (S + 1) / 2 keys per token)
QWEN_TRAIN_2048 = 4_236_902_400 + 125_890_560
# starcoder2-7b, 3 layers: q 4608 x 4608 = 21,233,664, k and v 4608 x 512 =
# 2,359,296 each (4 KV heads of 128), o 21,233,664; GELU MLP 2 x 4608 x
# 18432 = 169,869,312; one layer 217,055,232, three 651,165,696; head 4608
# x 49152 = 226,492,416.
STARCODER_MATMUL = 877_658_112
STARCODER_TRAIN_4096 = 5_265_948_672 + 3 * 3 * 2 * 4608 * 4097


@pytest.mark.parametrize("name,matmul,seq,per_token", [
    ("qwen1.5-4b", QWEN_MATMUL, 2048, QWEN_TRAIN_2048),
    ("starcoder2-7b", STARCODER_MATMUL, 4096, STARCODER_TRAIN_4096),
])
def test_train_flops_per_token(name, matmul, seq, per_token):
    m = STARCODER if name == "starcoder2-7b" else model(name)
    assert flops.matmul_params(m) == matmul
    assert flops.train_flops_per_token(m, seq) == per_token


def test_v5e_peak_row_and_unknown_kind():
    row = peaks_for("TPU v5 lite")
    assert row["flops_bf16"] == 197e12 and row["hbm_bw"] == 819e9
    assert "TPU v5e" in row["source"]
    with pytest.raises(KeyError):
        peaks_for("cpu")
