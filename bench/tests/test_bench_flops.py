"""Operation and byte counts (bench/flops.py) against hand counts."""
import pytest

from bench import flops


def test_vgg11_forward_is_0_343_gflop_per_sample():
    # conv MACs at 32x32 input: 3*64*9*1024 + 64*128*9*256 + 128*256*9*64
    # + 256*256*9*64 + 256*512*9*16 + 512*512*9*16 + 2 * 512*512*9*4;
    # fc: 512*4096 + 4096*4096 + 4096*10
    conv = (3 * 64 * 9 * 1024 + 64 * 128 * 9 * 256 + 128 * 256 * 9 * 64
            + 256 * 256 * 9 * 64 + 256 * 512 * 9 * 16 + 512 * 512 * 9 * 16
            + 2 * 512 * 512 * 9 * 4)
    fc = 512 * 4096 + 4096 * 4096 + 4096 * 10
    assert flops.vgg11_forward_flops() == 2 * (conv + fc) == 343_359_488


def test_vgg11_train_is_three_passes_less_the_first_input_gradient():
    fwd = flops.vgg11_forward_flops()
    first = 2 * 3 * 64 * 9 * 32 * 32
    assert flops.vgg11_train_flops() == 3 * fwd - first


def test_vgg11_parameter_count():
    n = 0
    for kind, d in flops.vgg11_layers():
        if kind == "conv":
            n += 9 * d["ci"] * d["co"] + d["co"]
        else:
            n += d["si"] * d["so"] + d["so"]
    assert n == 28_144_010


@pytest.mark.parametrize("m,k,n", [(95, 512, 4096), (570, 4096, 4096),
                                   (8, 4096, 10)])
def test_gemm_ops_and_bytes(m, k, n):
    ops, nbytes = flops.gemm(m, k, n)
    assert ops == 2 * m * k * n
    assert nbytes == 4 * (m * k + k * n + m * n)


def test_fc_kernel_calls_cover_fwd_dx_dw_of_three_layers():
    calls = flops.fc_kernel_calls(570)
    assert [c[0] for c in calls] == [f"fc{i}.{p}" for i in range(3)
                                     for p in ("fwd", "dx", "dw")]
    name, ops, nbytes = calls[3]          # fc1 forward: 570x4096 @ 4096x4096
    assert name == "fc1.fwd"
    assert ops == 2 * 570 * 4096 * 4096 + 570 * 4096
    assert nbytes == 4 * (570 * 4096 + 4096 * 4096 + 570 * 4096 + 4096)


def test_least_time_names_its_bound():
    peak = flops.peaks("TPU v5 lite")
    t, bound = flops.least_time(*flops.gemm(95, 512, 4096), peak)
    assert bound == "memory"
    assert t == pytest.approx(4 * (95 * 512 + 512 * 4096 + 95 * 4096)
                              / 819e9)
    t, bound = flops.least_time(*flops.gemm(4096, 4096, 4096), peak)
    assert bound == "compute"
    assert t == pytest.approx(2 * 4096 ** 3 / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
