"""The Mixtral model module pinned to the code it replaced: on the tiny
shapes and one seed it draws the same weights bit for bit and judges a
fixed served sequence with the same gaps, and at Mixtral's widths it
counts the same FLOPs and bytes.  The digests, gaps and counts below were
read from the benchmark's Mixtral code before it moved into its model
module."""
import hashlib

import jax
import numpy as np

from tiny_bench import MIXTRAL, TINY_CONFIG

SEED = 2**31 + 17
TINY = MIXTRAL.dims(TINY_CONFIG)
MIXTRAL_4L = MIXTRAL.Dims(layers=4, d=4096, heads=32, kv_heads=8,
                          head_dim=128, d_ff=14336, experts=8, top_k=2,
                          vocab=32000, eps=1e-5, theta=1e6)


def digest(tree) -> str:
    """sha256 over every leaf's path, dtype, shape and bytes."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_mixtral_module_draws_the_same_weights():
    assert TINY == MIXTRAL.Dims(layers=2, d=64, heads=4, kv_heads=2,
                                head_dim=16, d_ff=128, experts=4, top_k=2,
                                vocab=256, eps=1e-5, theta=10000.0)
    key = MIXTRAL.base_key(SEED)
    assert digest(MIXTRAL.draw_layer(key, 1, TINY)) == (
        "072958d374a46ba129798b7846ba614ec812f187323040e41c1ea02903ccdc06")
    assert digest(MIXTRAL.draw_base(key, TINY)) == (
        "22c3de8b0d868e7543235f4a824eefad514c329c89a7216faa45d402740158d4")


def test_mixtral_module_judges_with_the_same_gaps():
    prompt = (np.arange(13, dtype=np.int32) * 37 + 5) % 256
    served = [3, 100, 17, 250, 5, 64]
    gaps, ctl = MIXTRAL.judge(TINY, SEED, [(prompt, served)], 48,
                              control=True)
    # float32 at HIGHEST on the CPU: equal to the old code's to rounding
    np.testing.assert_allclose(gaps[0], [
        2.5509960651397705, 5.095823287963867, 2.457104444503784,
        3.7492470741271973, 3.8807899951934814, 2.6853370666503906],
        rtol=1e-6)
    np.testing.assert_allclose(ctl[0], [
        0.0, 0.0, 0.45505738258361816, 0.6663522720336914,
        0.250962495803833, 0.0], rtol=1e-6, atol=1e-6)


def test_mixtral_module_counts_the_same_work():
    assert MIXTRAL.prefill_flops(MIXTRAL_4L, 300) == 949_534_720_000
    assert MIXTRAL.prefill_flops(MIXTRAL_4L, 1024) == 3_264_739_278_848
    assert MIXTRAL.prefill_flops(TINY, 20) == 5_075_968
    assert MIXTRAL.decode_flops(MIXTRAL_4L, 0) == 3_416_588_288
    assert MIXTRAL.decode_flops(MIXTRAL_4L, 1023) == 3_483_631_616
    assert MIXTRAL.decode_flops(TINY, 30) == 295_424
    assert MIXTRAL.expert_ffn_work(MIXTRAL_4L, 128, 8) == (
        45_097_156_608, 2_820_669_440)
    assert MIXTRAL.expert_ffn_work(TINY, 8, 4) == (393_216, 198_656)
