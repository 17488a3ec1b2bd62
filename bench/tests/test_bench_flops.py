"""The analytic forward count against XLA's own count of the compiled
forward pass (which also counts the normalisation and activations, so the
analytic count sits a few percent below it)."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench_testkit import ROOT, bench_modules


CONFIG = ROOT / "bench/configs/ams-deeplabv3-mnv2.json"


def _match_xla(student: dict, height: int, wid: int) -> None:
    from repro.models.seg.student import SegConfig, make_student, seg_forward

    with bench_modules(ROOT):
        import flops

        cfg = SegConfig(width=student["width"], n_classes=student["n_classes"],
                        stem=student["stem"], head=student["head"],
                        blocks=tuple(map(tuple, student["blocks"])))
        params = jax.eval_shape(lambda: make_student(cfg,
                                                     jax.random.PRNGKey(0)))
        img = jax.ShapeDtypeStruct((1, height, wid, 3), jnp.float32)
        compiled = jax.jit(lambda p, x: seg_forward(cfg, p, x)).lower(
            params, img).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        mine = flops.forward_flops(student, height, wid)
        assert 0.85 * cost["flops"] <= mine <= cost["flops"]
        assert flops.train_flops_per_image(student, height, wid) == 3 * mine


@pytest.mark.parametrize("width,height,wid", [(1.0, 32, 64), (4.0, 64, 128),
                                              (10.0, 64, 128)])
def test_forward_flops_match_xla(width, height, wid):
    _match_xla({"width": width, "n_classes": 5, "in_channels": 3,
                "stem": 16, "head": 64,
                "blocks": [[3, 24, 2], [3, 24, 1], [3, 32, 2], [3, 32, 1]]},
               height, wid)


def test_configuration_flops_match_xla():
    """The benchmark's MobileNetV2 student, all 17 blocks, at 64x128."""
    _match_xla(json.loads(CONFIG.read_text())["student"], 64, 128)


def test_paper_point_count():
    with bench_modules(ROOT):
        import flops

        student = json.loads(CONFIG.read_text())["student"]
        # 2.58 GFLOP of convolutions and upsampling per 256x512 image
        assert flops.forward_flops(student, 256, 512) == 2_575_990_784
