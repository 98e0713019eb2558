"""The op and byte counters against counts worked out by hand."""
import cellfiles
from harness import cells, counts, runner


def test_decode_call_by_hand():
    # one decode step of qwen2.5-3b's up projection: 32 slot rows,
    # K 2048, N 11008, w4a4
    m, k, n = 32, 2048, 11008
    assert counts.matmul_ops(m, k, n) == 1_442_840_576
    # activation codes 32*2048/2 + weight codes 2048*11008/2 + f32 out
    # 32*11008*4 + scales (32 + 11008)*4
    assert counts.matmul_bytes(m, k, n, 4, 4) == \
        32_768 + 11_272_192 + 1_409_024 + 44_160
    peaks = cells.peaks("TPU v5 lite")
    t, bound = counts.least_time_s([(m, k, n, 1)], peaks, 4, 4)
    assert bound == "memory"
    assert abs(t - 12_758_144 / 819e9) < 1e-15


def test_conv_call_by_hand():
    # resnet18's first 3x3 conv of stage 0 at batch 256: rows are the
    # 256*32*32 output pixels, K = 3*3*64, N = 64
    cfg, _ = runner.effective(cellfiles.find("resnet18-cifar100.batch1024"),
                              False)
    calls = counts.cnn_calls(cfg, 256, 1)
    assert calls[1] == (262_144, 576, 64, 1)
    m, k, n, _ = calls[1]
    assert counts.matmul_ops(m, k, n) == 19_327_352_832
    assert counts.matmul_bytes(m, k, n, 4, 4) == \
        75_497_472 + 18_432 + 67_108_864 + 1_048_832
    peaks = cells.peaks("TPU v5 lite")
    t, bound = counts.least_time_s([calls[1]], peaks, 4, 4)
    assert bound == "memory"            # 135 ops per byte < 480
    # a stride-2 conv halves the output side; its 1x1 shortcut too
    names = [l["name"] for l in counts.resnet_layers(cfg)]
    assert names[:4] == ["stem", "s0b0c1", "s0b0c2", "s0b1c1"]
    i = names.index("s1b0ds")
    assert calls[i] == (256 * 16 * 16, 64, 128, 1)


def test_lm_calls_per_step():
    cfg, mix = runner.effective(cellfiles.find("qwen2.5-3b.decode-batch"), False)
    calls = counts.lm_calls(cfg, mix["slots"], 10)
    assert [(k, n) for _, k, n, _ in calls] == [
        (2048, 2048), (2048, 256), (2048, 256), (2048, 2048),
        (2048, 11008), (2048, 11008), (11008, 2048)]
    assert all(m == 32 and c == 280 for m, _, _, c in calls)
    per_token = counts.lm_int_ops_per_token(cfg)
    assert per_token == 28 * 2 * (2 * 2048 * 2048 + 2 * 2048 * 256
                                  + 3 * 2048 * 11008)


def test_resnet18_is_the_papers_network():
    # the configuration file builds exactly the program's Table II
    # ResNet18 (11.21 M conv and dense weights; Table II's 11.58 M also
    # counts biases and batch norm)
    from repro.core.workloads import resnet18, total_params
    cell = cellfiles.find("resnet18-cifar100.batch1024")
    cfg, _ = runner.effective(cell, False)
    specs = cells.driver(cell).layer_specs(cfg)
    assert specs == resnet18(100, 32)
    assert total_params(specs) == 11_210_432
