"""The shape-FLOPs functions against a layer-by-layer count by hand."""
from benchmark.flops import resnet20


def test_resnet20_layer_by_layer():
    by = dict(resnet20.forward_macs_by_layer())
    assert by["stem.conv3x3"] == 32 * 32 * 3 * 16 * 9 == 442_368
    # stage 1: six 3x3 convolutions 16->16 at 32x32
    s1 = [v for k, v in by.items() if k.startswith("stage1")]
    assert s1 == [2_359_296] * 6
    # stage 2: 16->32 stride 2 (16x16 out), 32->32, 1x1 shortcut, then
    # four 32->32
    assert by["stage2.block0.conv0"] == 16 * 16 * 16 * 32 * 9 == 1_179_648
    assert by["stage2.block0.conv1"] == 16 * 16 * 32 * 32 * 9 == 2_359_296
    assert by["stage2.block0.shortcut1x1"] == 16 * 16 * 16 * 32 == 131_072
    assert by["stage3.block0.conv0"] == 8 * 8 * 32 * 64 * 9 == 1_179_648
    assert by["stage3.block0.shortcut1x1"] == 8 * 8 * 32 * 64 == 131_072
    assert by["stage3.block2.conv1"] == 8 * 8 * 64 * 64 * 9 == 2_359_296
    assert by["head.dense"] == 640
    assert len(by) == 1 + 18 + 2 + 1
    # 0.442 + 14.156 + 13.107 + 13.107 M + 640: telemetry/costs.py's
    # 40.8e6 multiply-accumulates per image
    assert resnet20.forward_macs_per_image() == 40_813_184
    assert resnet20.train_flops_per_image() == 6 * 40_813_184
