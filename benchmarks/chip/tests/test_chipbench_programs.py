"""The device programs that the trace metrics find by name keep the names
jit gives them: the serving engine's prefill and decode and the KMeans map,
lowered at test size on the paths the cells run, against each metric
file's PROGRAM."""
import re

import jax
import numpy as np

from chipbench_tiny import CHIP, harness, tiny_cell


def _program(metric):
    return harness.load_module(CHIP / "metrics" / f"{metric}.py",
                               "chip_metric").PROGRAM


def _name(jitted, *args):
    text = jitted.lower(*args).compile().as_text()
    return harness.sibling("trace").program_name(
        re.match(r"HloModule (\w+)", text).group(1))


def test_serving_programs():
    from repro.core import PilotSession
    from repro.serving import ServingEngine
    cell = tiny_cell("mamba8.chat_closed")
    driver = cell.driver()
    model = driver.program_model(cell.config)
    rows = cell.config["batch_size"]
    with PilotSession() as s:
        (pilot,) = s.add_pilots(1, memory_gb=0.25)
        with ServingEngine(s, model, batch_size=rows, max_len=32) as eng:
            eng.deploy()
            rt = eng._pilot_runtime(pilot)
            one = eng._prefill_batch(np.zeros((1, 8), np.int32))
            assert _name(rt.prefill, rt.params, one) == _program(
                "serve_prefill_share")
            full = eng._prefill_batch(np.zeros((rows, 8), np.int32))
            _, cache = jax.eval_shape(rt.prefill, rt.params, full)
            tokens = jax.ShapeDtypeStruct((rows, 1), np.int32)
            positions = jax.ShapeDtypeStruct((rows,), np.int32)
            assert _name(rt.decode, rt.params, cache, tokens,
                         positions) == _program("serve_decode_roofline")


def test_kmeans_map_program():
    from repro.core import analytics, mapreduce
    c = tiny_cell("kmeans_i.resident").config
    points = jax.ShapeDtypeStruct((c["points"] // c["partitions"], c["dim"]),
                                  np.float32)
    centroids = jax.ShapeDtypeStruct((c["clusters"], c["dim"]), np.float32)
    jitted = mapreduce._jit_cached(analytics.assign_partial)
    assert _name(jitted, points, centroids) == _program(
        "kmeans_assign_roofline")
