"""FLOP counts from shapes against figures worked by hand, and the peak
table's refusal of an unknown device."""
import json
import os

import pytest

from bench import flops, peaks
from bench.reference import dense_decoder, mamba2

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(REPO, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_qwen_train_step_flops():
    c = _config("qwen1.5-0.5b")
    assert flops.n_params(dense_decoder, c) == 463_987_712
    # 6 N D: 6 * 463,987,712 * (6 * 2048) = 3.4209e13;
    # causal scores and values: 3 (fwd+bwd) * 2 products * 2 flops
    # * (2048^2 / 2) pairs * 16 heads * 64 * 24 layers * 6 rows = 3.7108e12
    six_nd = 6 * 463_987_712 * 6 * 2048
    attn = 3 * 2 * 2 * (2048 ** 2 / 2) * 16 * 64 * 24 * 6
    assert six_nd == pytest.approx(3.4209e13, rel=1e-4)
    assert attn == pytest.approx(3.71e12, rel=1e-3)
    assert flops.train_step(dense_decoder, c, 6, 2048) == pytest.approx(
        six_nd + attn, rel=1e-12)


def test_mamba2_train_step_flops():
    c = _config("mamba2-780m")
    n = flops.n_params(mamba2, c)
    assert n == 428_690_304          # 24 layers, tied, 50,280 rows
    six_nd = 6 * n * 4 * 2048
    assert six_nd == pytest.approx(2.107e13, rel=1e-3)
    # SSD per layer per row, chunk Q = 256, S = 2048, H = 48, P = 64,
    # N = 128, G = 1: C.B and the masked mixing over S*Q/2 pairs
    # (2 flops * pairs * (N*G + H*P)), read-out and update 2 * 2*S*H*P*N
    pairs = 2048 * 256 / 2
    per_layer = 2 * pairs * (128 + 48 * 64) + 2 * 2 * 2048 * 48 * 64 * 128
    ssd = 3 * 4 * 24 * per_layer
    assert ssd == pytest.approx(1.4123e12, rel=1e-3)
    assert flops.train_step(mamba2, c, 4, 2048) == pytest.approx(
        six_nd + ssd, rel=1e-12)


def test_serve_request_flops_counts_prefill_decode_and_head():
    c = _config("qwen1.5-0.5b")
    d = dense_decoder.dims(c)
    body = 2 * (463_987_712 - d.V * d.D)
    head = 2 * d.V * d.D
    # 10 prompt tokens, 4 cached, 3 generated: 6 prefilled + 2 decoded
    attn_keys = sum(t + 1 for t in range(4, 12))
    want = 8 * body + 3 * head + 4 * attn_keys * d.H * d.dh * d.L
    assert flops.serve_request(dense_decoder, c, 10, 4, 3) == pytest.approx(
        want, rel=1e-12)


def test_unknown_device_is_an_error():
    assert peaks.peak("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_active_params_replace_the_parameter_count():
    """A family that defines ``active_params`` (a mixture of experts: k of
    E routed experts) is counted by it in training and serving; one that
    does not, by every parameter."""
    c = _config("qwen1.5-0.5b")
    d = dense_decoder.dims(c)
    total = flops.n_params(dense_decoder, c)
    assert flops.model_params(dense_decoder, c) == total
    active = total - 300_000_000

    class Sparse:
        dims = staticmethod(dense_decoder.dims)
        param_specs = staticmethod(dense_decoder.param_specs)

        @staticmethod
        def active_params(c):
            return active

    assert flops.model_params(Sparse, c) == active
    attn = 3 * 2 * 2 * (2048 ** 2 / 2) * d.H * d.dh * d.L * 6
    assert flops.train_step(Sparse, c, 6, 2048) == pytest.approx(
        6 * active * 6 * 2048 + attn, rel=1e-12)
    body = 2 * (active - d.V * d.D)
    want = 8 * body + 3 * 2 * d.V * d.D + 4 * sum(
        t + 1 for t in range(4, 12)) * d.H * d.dh * d.L
    assert flops.serve_request(Sparse, c, 10, 4, 3) == pytest.approx(
        want, rel=1e-12)
