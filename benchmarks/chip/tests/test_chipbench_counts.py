"""FLOP and byte counting and the peaks table, against hand counts."""

from __future__ import annotations

import pytest

from benchmarks.chip import flops, spec

BENCH = spec.Benchmark()
GLM = BENCH.reference("chatglm3-6b-32k")
OLMOE = BENCH.reference("olmoe-1b-7b")

DENSE = {"d": 4, "heads": 2, "kv_heads": 1, "head_dim": 2, "ffn": 6,
         "layers": 1, "vocab": 10}
MOE = {"d": 4, "heads": 2, "kv_heads": 2, "head_dim": 2, "ffn": 3,
       "experts": 4, "top_k": 2, "layers": 2, "vocab": 10}


def test_dense_flops_by_hand():
    # q 4x4, k and v 4x2 each, o 4x4: 2*(16+8+8+16) = 96
    # scores + PV over (3+1)/2 = 2 keys: 2 * 2*2*2*2 = 32
    # SwiGLU 3 matmuls 4x6: 2*72 = 144; head 4x10: 80
    assert GLM.forward_flops_per_token(DENSE, 3) == 96 + 32 + 144 + 80
    assert flops.train_flops_per_token(GLM, DENSE, 3) == 3 * (
        96 + 32 + 144 + 80)


def test_moe_flops_by_hand():
    # per layer: projections 2*(16+16+16+16) = 128; attention over
    # (4+1)/2 keys: 2 * 2*2*2*2.5 = 40; router 2*4*4 = 32; two experts of
    # 3 matmuls 4x3: 2 * 2*36 = 144
    per_layer = 128 + 40 + 32 + 144
    assert OLMOE.forward_flops_per_token(MOE, 4) == 2 * per_layer + 80


def test_published_counts():
    olmoe = OLMOE.dims(BENCH.config("olmoe-1b-7b"))
    glm = GLM.dims(BENCH.config("chatglm3-6b-32k"))
    assert flops.train_flops_per_token(OLMOE, olmoe, 4096) == pytest.approx(
        531.05e6, rel=1e-4)
    assert flops.train_flops_per_token(GLM, glm, 8192) == pytest.approx(
        1624.79e6, rel=1e-4)


DISPATCH = ("%dispatch_pallas.29 = bf16[1,8,80,2,128]{4,3,2,1,0:T(2,128)(2,1)"
            "S(1)} custom-call(s32[640]{0:T(1024)S(1)} %reshape.2706, bf16[1,"
            "256,2,128]{3,2,1,0:T(2,128)(2,1)S(1)} %copy.877, f32[1,8,80,1,1]"
            "{4,3,2,1,0:T(1,128)S(1)} %copy.878), custom_call_target=\""
            "tpu_custom_call\"")
COMBINE = ("%combine_pallas.20 = bf16[1,256,2,128]{3,2,1,0} custom-call(s32"
           "[512]{0} %a, s32[512]{0} %b, bf16[1,8,80,2,128]{4,3,2,1,0} %c, "
           "f32[1,2,256,1,1]{4,3,2,1,0} %d), custom_call_target=\"x\"")


def test_kernel_bytes_by_hand():
    slab = 1 * 8 * 80 * 256 * 2
    assert flops.kernel_bytes("dispatch", DISPATCH) == (
        slab + 640 * 4 + 256 * 256 * 2 + 640 * 4)
    # 512 (token, k) pairs each read one 256-wide bf16 row
    assert flops.kernel_bytes("combine", COMBINE) == (
        256 * 256 * 2 + 512 * 4 * 2 + 512 * 4 + 512 * 256 * 2)
    assert flops.kernel_flops("dispatch", DISPATCH) == 8 * 80 * 256
    assert flops.kernel_flops("combine", COMBINE) == 2 * 2 * 256 * 256


def test_peaks_by_device_kind():
    bench = spec.Benchmark()
    v5e = bench.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bench.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        bench.peaks("cpu")
