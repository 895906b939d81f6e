"""Model FLOPs and least bytes of mamba2 against a count by hand."""
from chipbench.flops import mamba2 as flops


def test_forward_flops_by_hand(small_config):
    # d=64, e=128, N=16, G=1, H=2, Q=32, K=4, V=512, 2 layers
    d_in_proj = 2 * 128 + 2 * 16 + 2  # 290
    proj = 2 * 64 * d_in_proj + 2 * 128 * 64  # 53504
    conv = 2 * 4 * (128 + 32)  # 1280
    ssd = 2 * 32 * 16 + 2 * 32 * 128 + 4 * 16 * 128  # 1024 + 8192 + 8192
    head = 2 * 64 * 512
    want = 2 * (proj + conv + ssd) + head
    assert flops.forward_per_token(small_config) == want == 209920
    assert flops.train_per_token(small_config) == 3 * want


def test_dithered_backward_bytes_by_hand(small_config):
    T = 128
    # in projection K=64, N=290; out projection K=128, N=64; bf16
    in_proj = 2 * (T * 290 + 2 * T * 64 + 2 * 64 * 290)
    out_proj = 2 * (T * 64 + 2 * T * 128 + 2 * 128 * 64)
    assert flops.dithered_backward_bytes(small_config, T) == \
        2 * (in_proj + out_proj)
