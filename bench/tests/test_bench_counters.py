"""Operations and bytes of a decode step against hand-computed values, and
the peaks table."""
import json

import pytest

from checkout import BENCH
from harness import counters
from harness.spec import Spec

ONE_CHIP = json.loads((BENCH / "configs" / "granite-8b-1chip.json")
                      .read_text())
FULL = dict(ONE_CHIP, num_hidden_layers=36)      # all of its layers

# granite-8b at published widths: d 4096, 32 query heads and 8 key/value
# heads of 128, d_ff 14336, vocab 49152, bf16.
ATTN = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096      # 41,943,040
MLP = 3 * 4096 * 14336                                   # 176,160,768
LAYER = ATTN + MLP + 2 * 4096                            # 218,112,000
EMBED = 49152 * 4096                                     # 201,326,592


def test_shapes_of_granite_8b():
    s = counters.shapes(ONE_CHIP)
    assert s["params_per_layer"] == LAYER == 218_112_000
    assert s["matmul_per_layer"] == ATTN + MLP == 218_103_808
    assert s["item"] == 2


def test_weight_bytes_match_the_served_model():
    # the one-chip engine reported 7,784,898,560 B of weights
    assert counters.weight_bytes(ONE_CHIP) == 7_784_898_560
    assert counters.weight_bytes(FULL) == 2 * (36 * LAYER + 2 * EMBED
                                               + 4096)
    assert counters.kv_bytes_per_token(ONE_CHIP) == 16 * 2 * 8 * 128 * 2


def test_decode_step_of_16_layers_with_32_live_rows():
    """32 live rows, each attending to 1,024 tokens."""
    ctx = 32 * 1024
    weights = (16 * LAYER + EMBED + 4096) * 2     # all but the embedding
    embed_rows = 32 * 4096 * 2
    kv = ctx * 65_536                              # 64 KiB per token
    logits = 32 * 49152 * 2
    assert weights == 7_382_245_376
    assert counters.decode_bytes(ONE_CHIP, 32, ctx) == \
        weights + embed_rows + kv + logits == 9_533_136_896
    dense = 2 * (16 * (ATTN + MLP) + 4096 * 49152) * 32
    attn = 16 * 4 * 32 * 128 * ctx                 # scores and sum
    assert counters.decode_flops(ONE_CHIP, 32, ctx) == \
        dense + attn == 244_813_135_872


def test_decode_step_of_all_36_layers():
    got = counters.decode_bytes(FULL, 16, 16 * 9000)
    assert got == ((36 * LAYER + EMBED + 4096) * 2 + 16 * 4096 * 2
                   + 16 * 9000 * 36 * 2 * 8 * 128 * 2 + 16 * 49152 * 2)


def test_peaks_table_is_keyed_by_device_kind(tmp_path):
    spec = Spec(BENCH.parent)
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in the peaks table"):
        spec.peaks("TPU v4")
