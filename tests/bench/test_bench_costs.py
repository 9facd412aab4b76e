"""Operations and bytes from shapes, against figures worked out by hand."""
import json
import os

import pytest

from harness import costs

HERE = os.path.dirname(__file__)
CONFIGS = (os.path.join(HERE, "..", "..", "bench", "configs"),
           # sizes of a GRU stack that no cell serves, for the GRU path
           os.path.join(HERE, "data", "configs"))


def conf(name):
    for d in CONFIGS:
        path = os.path.join(d, f"{name}.json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise FileNotFoundError(name)


def test_lstm_rnnt_weight_bytes():
    c = conf("lstm-rnnt")
    # layer 0: W 2048 x 8192 + R 640 x 8192 + W_proj 2048 x 640
    assert costs.layer_weight_bytes(c, 0) == 23_330_816
    # layers 1-9: W 640 x 8192 + R 640 x 8192 + W_proj 2048 x 640
    assert costs.layer_weight_bytes(c, 5) == 11_796_480
    assert costs.stack_weight_bytes(c) == 129_499_136
    assert costs.head_bytes(c) == 5_242_880  # bf16 640 x 4096
    assert c["weight_bytes"] == {"int8_stack": 129_499_136,
                                 "bf16_head": 5_242_880}


def test_gru_rnnt_weight_bytes():
    c = conf("gru-rnnt")
    # every layer: W 2048 x 6144 + R 2048 x 6144
    assert costs.layer_weight_bytes(c, 0) == 25_165_824
    assert costs.stack_weight_bytes(c) == 251_658_240
    assert costs.head_bytes(c) == 16_777_216  # bf16 2048 x 4096
    assert c["weight_bytes"] == {"int8_stack": 251_658_240,
                                 "bf16_head": 16_777_216}


@pytest.mark.parametrize("name,per_layer,total", [
    ("lstm-rnnt", 640 + 2 * 2048, 47_364),
    ("gru-rnnt", 2048, 20_484)])
def test_state_bytes(name, per_layer, total):
    c = conf(name)
    assert costs.state_bytes_per_stream_layer(c) == per_layer
    assert 10 * per_layer + 4 == total == c["state_bytes_per_stream"]


def test_ops_per_token_and_head():
    c = conf("lstm-rnnt")
    assert costs.int8_ops_per_token(c) == 2 * 129_499_136
    assert costs.head_ops_per_row(c) == 2 * 640 * 4096
    g = conf("gru-rnnt")
    assert costs.head_ops_per_row(g) == 2 * 2048 * 4096


def test_scan_kernel_decode_launch_is_bandwidth_bound():
    c = conf("lstm-rnnt")
    ops, nbytes = costs.scan_kernel_cost(c, batch=8, steps=1)
    # R_cat 640 x 8192 + W_proj 2048 x 640, read once per launch
    weights = 640 * 8192 + 2048 * 640
    assert ops == 2 * 8 * weights
    assert nbytes == weights + 8 * (8192 * 4 + 640) + 2 * 8 * (640 + 4096)
    t, bound = costs.least_time_s(ops, nbytes, 393e12, 819e9)
    assert bound == "bytes" and t == pytest.approx(nbytes / 819e9)


def test_scan_kernel_pads_batch_to_eight_rows():
    c = conf("gru-rnnt")
    assert costs.scan_kernel_cost(c, 1, 8) == costs.scan_kernel_cost(c, 8, 8)
    ops, _ = costs.scan_kernel_cost(c, 8, 8)
    assert ops == 2 * 8 * 8 * 2048 * 6144


def test_least_time_names_compute_bound():
    t, bound = costs.least_time_s(393e12, 1.0, 393e12, 819e9)
    assert (t, bound) == (1.0, "ops")
