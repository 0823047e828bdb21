"""Operation and byte counts from logical shapes, for every layer kind.

The counts of the accepted configurations are pinned to the values the
cost model gave before it took 1-D layers; the 1-D shapes are those of
the program's own shape inference (``snn._layer_shapes``, ``snn._fan_in``).
"""
import dataclasses

import pytest

import harness
from costs import snn as costs
from repro.models import snn

DATA = harness.BENCH / "tests" / "data"

PINNED_B256 = {
    "2layer-snn": [
        {"kind": "fc", "B": 256, "P": 1, "K": 784, "C": 100, "N_in": 784},
    ],
    "6layer-dcsnn": [
        {"kind": "conv2d", "B": 256, "P": 576, "K": 25, "C": 12, "N_in": 784},
        {"kind": "conv2d", "B": 256, "P": 100, "K": 108, "C": 24, "N_in": 1728},
        {"kind": "fc", "B": 256, "P": 1, "K": 600, "C": 128, "N_in": 600},
    ],
}
PINNED_STEP_FLOPS_B256 = {  # (train, infer)
    "2layer-snn": (120422400.0, 40140800.0),
    "6layer-dcsnn": (781516800.0, 260505600.0),
}


def _file(cfg: snn.SNNConfig) -> dict:
    """The shape keys of a configuration file, from a program config."""
    return {"input_shape": list(cfg.input_shape),
            "layers": [dataclasses.asdict(l) for l in cfg.layers]}


@pytest.mark.parametrize("name", sorted(PINNED_B256))
def test_accepted_configs_count_as_before(name):
    c = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    assert costs.layers(c, 256) == PINNED_B256[name]
    assert (costs.step_flops(c, 256, True), costs.step_flops(c, 256, False)) == \
        PINNED_STEP_FLOPS_B256[name]


@pytest.mark.parametrize("cfg", [
    snn.fault_csnn(),
    snn.fault_csnn(length=64),
    snn.SNNConfig(name="strided-1d", input_shape=(101, 3), layers=(
        snn.SNNLayerSpec("conv1d", out_features=5, kernel=4, stride=3),
        snn.SNNLayerSpec("pool1d", pool=3),
        snn.SNNLayerSpec("conv1d", out_features=7, kernel=2, stride=1),
        snn.SNNLayerSpec("fc", out_features=9))),
], ids=lambda cfg: f"{cfg.name}-{cfg.input_shape[0]}")
def test_1d_layers_follow_the_program_shapes(cfg):
    got = costs.layers(_file(cfg), 4)
    shapes = snn._layer_shapes(cfg)
    ins = [tuple(cfg.input_shape)] + shapes[:-1]
    want = []
    for spec, in_shape, out_shape in zip(cfg.layers, ins, shapes):
        if spec.kind.startswith("pool"):
            continue
        n_in = 1
        for d in in_shape:
            n_in *= d
        P = out_shape[0] if spec.kind == "conv1d" else 1
        want.append({"kind": spec.kind, "B": 4, "P": P, "K": snn._fan_in(spec, in_shape),
                     "C": spec.out_features, "N_in": n_in})
    assert got == want


def test_csnn_file_counts():
    c = harness.load_json(DATA / "5layer-csnn.json")
    conv1, conv2, fc = costs.layers(c, 256)
    assert (conv1["P"], conv1["K"], conv1["C"], conv1["N_in"]) == (253, 14, 8, 1024)
    assert (conv2["P"], conv2["K"], conv2["C"], conv2["N_in"]) == (61, 40, 16, 1008)
    assert (fc["P"], fc["K"], fc["C"], fc["N_in"]) == (1, 480, 64, 480)


@pytest.mark.parametrize("kind", ["conv3d", "pool3d", "attention"])
def test_unknown_layer_kind_raises(kind):
    c = {"input_shape": [8, 8, 8, 1], "layers": [{"kind": kind, "out_features": 2}]}
    with pytest.raises(ValueError, match="no cost model"):
        costs.layers(c, 1)
