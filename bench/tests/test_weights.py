"""Weights drawn across four chips (forced CPU devices, in a subprocess)
equal the one-device draw bit for bit, no device holds a leaf whole where
the device count divides one of its axes, and the reference's train step
on weights so placed never gathers a stacked ``layers/`` leaf whole."""
import os
import re
import subprocess
import sys

from bench.tests import tiny

SEED = 3_000_000_019           # wider than 32 bits
CONFIGS = ("qwen1.5-0.5b", "mamba2-780m")


def _tiny_config(name: str):
    import importlib

    from bench import harness

    c = harness.load_json(tiny.REPO, "bench", "configs", f"{name}.json")
    ref = importlib.import_module(f"bench.reference.{c['family']}")
    return tiny.cut(c, ref.TINY["config"]), ref


def check_four_devices() -> None:
    import jax
    import numpy as np

    from bench import weights

    devices = jax.devices()
    assert len(devices) == 4, devices
    for name in CONFIGS:
        c, ref = _tiny_config(name)
        specs = ref.param_specs(c)
        one = weights.make(specs, SEED)
        four = weights.make(specs, SEED, devices=devices)
        whole = held = 0
        for k, (shape, _) in specs.items():
            a, b = np.asarray(one[k]), np.asarray(four[k])
            assert a.dtype == b.dtype and np.array_equal(
                a.view(np.uint32), b.view(np.uint32)), (name, k)
            divides = any(s % 4 == 0 for s in shape)
            shards = four[k].addressable_shards
            assert {s.device for s in shards} == set(devices)
            biggest = max(s.data.size for s in shards)
            assert biggest == (a.size // 4 if divides else a.size), (k, shape)
            whole += a.nbytes
            held += biggest * a.itemsize
        assert held < whole / 2, (name, held, whole)


def check_reference_step_placed() -> None:
    """The reference's AdamW step, compiled on four devices over placed
    weights and a replicated batch of the tiny training cell: no all-gather
    yields a whole stacked leaf, and a device's temporaries stay under the
    whole float32 model."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench import weights
    from bench.reference import training

    devices = jax.devices()
    opt = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
           "weight_decay": 0.0}
    rep = NamedSharding(Mesh(np.asarray(devices), ("chips",)), P())
    for name in CONFIGS:
        c, ref = _tiny_config(name)
        # deep enough that the weights outweigh the activations, as at the
        # published sizes
        c[{"dense_decoder": "num_hidden_layers"}.get(c["family"],
                                                     "n_layer")] = 32
        specs = ref.param_specs(c)
        place = weights.placement(specs, devices)
        w = {k: jax.ShapeDtypeStruct(tuple(s[0]), jnp.float32,
                                     sharding=place[k])
             for k, s in specs.items()}
        tok = jax.ShapeDtypeStruct((4, 64), jnp.int32, sharding=rep)
        count = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
        step = training.make_step(ref, c, opt, "f32", 1)
        comp = step.lower(w, w, w, count, tok, tok).compile()
        stacked = {tuple(s[0]) for k, s in specs.items()
                   if k.startswith("layers/")}
        gathered = {tuple(int(d) for d in m.group(1).split(",") if d)
                    for m in re.finditer(r"= f32\[([\d,]*)\][^ ]* all-gather",
                                         comp.as_text())}
        assert not gathered & stacked, (name, gathered & stacked)
        model = sum(int(np.prod(s[0])) * 4 for s in specs.values())
        temps = comp.memory_analysis().temp_size_in_bytes
        assert temps < model, (name, temps, model)


def _in_four_devices(fn: str) -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([tiny.REPO,
                                           os.path.join(tiny.REPO, "src")]))
    p = subprocess.run(
        [sys.executable, "-c", f"from bench.tests.test_weights import {fn}; "
         f"{fn}(); print('ok')"],
        cwd=tiny.REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0 and p.stdout.strip().endswith("ok"), \
        p.stderr[-4000:]


def test_weights_across_four_devices_equal_one_device_draw():
    _in_four_devices("check_four_devices")


def test_reference_step_gathers_no_stacked_leaf_whole():
    _in_four_devices("check_reference_step_placed")
