"""``bench/flops.py`` against a count by hand for qwen3-0.6b at seq 512."""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from bench import flops  # noqa: E402


def _cfg():
    with open(os.path.join(REPO, "bench", "configs", "qwen3-0.6b.json")) as f:
        return json.load(f)


def test_forward_flops_by_hand():
    # per layer: q 1024x2048, k and v 1024x1024 each, o 2048x1024
    # (2 FLOP per multiply-add), MLP 3 x 1024x3072, causal attention
    # 2 x (QK^T and PV) x 16 heads x 128 x 513 / 2; head 1024 x 151936
    q = 2 * 1024 * 2048
    kv = 2 * 2 * 1024 * 1024
    o = 2 * 2048 * 1024
    mlp = 3 * 2 * 1024 * 3072
    attn = 2 * 2 * 16 * 128 * 513 / 2
    head = 2 * 1024 * 151936
    want = 28 * (q + kv + o + mlp + attn) + head
    assert want == 1_250_803_712
    assert flops.forward_flops_per_token(_cfg(), 512) == want


def test_train_block_flops():
    block2 = {"block_microsteps": 2, "batch": 4, "seq_len": 512}
    assert flops.train_flops_per_block(_cfg(), block2) == \
        3 * 1_250_803_712 * 4096
