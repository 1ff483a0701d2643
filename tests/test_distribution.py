"""Multi-device distribution tests.  These re-exec python with
``--xla_force_host_platform_device_count=8`` so the main pytest process (and
all smoke tests) keep seeing exactly 1 device."""

import json
import subprocess
import sys
import textwrap

import pytest

from conftest import REPO_ROOT, cpu_child_env

BOOT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
"""


def run_py(body: str) -> dict:
    code = BOOT + textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=900,
        env=cpu_child_env(), cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


class TestShardingRules:
    def test_param_specs_resolve(self):
        out = run_py("""
            from repro.configs import get_config
            from repro.models import build_model, ModelOptions
            from repro.parallel.sharding import param_shardings, opt_shardings
            mesh = make_mesh((2, 4), ("data", "model"))
            cfg = get_config("glm4-9b").reduced()
            model = build_model(cfg, ModelOptions())
            pshape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
            ps = param_shardings(pshape, mesh)
            specs = {}
            import jax.tree_util as jtu
            for (path, s), (_, leaf) in zip(
                jtu.tree_flatten_with_path(ps)[0], jtu.tree_flatten_with_path(pshape)[0]
            ):
                key = "/".join(str(getattr(k, "key", k)) for k in path)
                specs[key] = str(s.spec)
            print(json.dumps(specs))
        """)
        assert "model" in out["layers/attn/wq"]
        assert "model" in out["embed/tokens"]
        # kv heads (4 reduced) divisible by model=4 -> sharded
        assert "model" in out["layers/attn/wk"]
        assert "model" not in out["final_norm/norm_scale"]
        assert "data" not in out["final_norm/norm_scale"]

    def test_kv_indivisible_degrades_to_replication(self):
        out = run_py("""
            from repro.parallel.sharding import resolve_spec, default_rules
            mesh = make_mesh((2, 4), ("data", "model"))
            rules = default_rules(mesh.axis_names)
            s1 = resolve_spec(("batch", None, "kv_heads", None), (8, 128, 2, 64), mesh, rules)
            s2 = resolve_spec(("batch", None, "kv_heads", None), (8, 128, 4, 64), mesh, rules)
            print(json.dumps({"indiv": str(s1), "div": str(s2)}))
        """)
        assert "model" not in out["indiv"]
        assert "model" in out["div"]

    def test_sharded_train_step_matches_single_device(self):
        """The pjit-sharded train step must be numerically equivalent to the
        unsharded one (same loss after 3 steps)."""
        out = run_py("""
            from repro.configs import get_config
            from repro.models import build_model, ModelOptions
            from repro.optim import AdamWConfig, init_opt_state
            from repro.train import make_train_step
            from repro.parallel import sharding as shd
            from repro.data import SyntheticDataset

            cfg = get_config("minicpm-2b").reduced()
            model = build_model(cfg, ModelOptions(compute_dtype="float32", remat=False))
            ds = SyntheticDataset(cfg.vocab, 16, 8)
            opt = AdamWConfig(lr=1e-3)

            # single device
            params = model.init(jax.random.PRNGKey(0))
            state = init_opt_state(params)
            step1 = make_train_step(model, opt, donate=False)
            for i in range(3):
                batch = {k: jnp.asarray(v) for k, v in ds.batch(i).items()}
                params, state, m1 = step1(params, state, batch)

            # sharded
            mesh = make_mesh((2, 4), ("data", "model"))
            params2 = model.init(jax.random.PRNGKey(0))
            state2 = init_opt_state(params2)
            with shd.activate(mesh):
                stepper = make_train_step(model, opt, mesh=mesh, donate=False)
                batch_shape = jax.eval_shape(lambda: {k: jnp.asarray(v) for k, v in ds.batch(0).items()})
                fn = stepper(batch_shape)
                for i in range(3):
                    batch = {k: jnp.asarray(v) for k, v in ds.batch(i).items()}
                    params2, state2, m2 = fn(params2, state2, batch)
            print(json.dumps({"l1": float(m1["loss"]), "l2": float(m2["loss"])}))
        """)
        assert abs(out["l1"] - out["l2"]) < 1e-4, out

    def test_trainer_state_sharded_at_init_and_restore(self):
        """Under a mesh the Trainer creates and restores state with the
        step's shardings, and a resumed run matches an uninterrupted one."""
        out = run_py("""
            import tempfile
            from repro.configs import get_config
            from repro.data import SyntheticDataset
            from repro.models import build_model, ModelOptions
            from repro.optim import AdamWConfig
            from repro.train import Trainer, TrainerConfig

            cfg = get_config("minicpm-2b").reduced()
            model = build_model(cfg, ModelOptions(compute_dtype="float32", remat=False))
            ds = SyntheticDataset(cfg.vocab, 16, 8)
            mesh = make_mesh((2, 4), ("data", "model"))

            def trainer(steps, ckpt_dir):
                return Trainer(model, ds, AdamWConfig(lr=1e-3), ckpt_dir=ckpt_dir,
                               cfg=TrainerConfig(total_steps=steps, ckpt_every=2,
                                                 log_every=1), mesh=mesh)

            def placed(state, shardings):
                return all(a.sharding.is_equivalent_to(s, a.ndim)
                           for a, s in zip(jax.tree.leaves(state),
                                           jax.tree.leaves(shardings)))

            resumed_dir = tempfile.mkdtemp()
            first = trainer(2, resumed_dir)
            fresh = first._init_state()
            first.run()
            second = trainer(4, resumed_dir)
            params, opt, start = second._restore_or_init()
            second.run()
            whole = trainer(4, tempfile.mkdtemp())
            whole.run()
            split = [len(a.sharding.device_set) > 1 for a in jax.tree.leaves(fresh)]
            print(json.dumps({
                "fresh": placed(fresh, first.state_shardings),
                "restored": placed((params, opt), second.state_shardings),
                "start": start, "any_split": any(split),
                "resumed": second.losses()[-1], "whole": whole.losses()[-1],
            }))
        """)
        assert out["fresh"] and out["restored"] and out["any_split"], out
        assert out["start"] == 2
        assert abs(out["resumed"] - out["whole"]) < 1e-5, out

    def test_zero1_opt_state_sharded_over_data(self):
        out = run_py("""
            from repro.parallel.sharding import opt_spec
            mesh = make_mesh((2, 4), ("data", "model"))
            s = opt_spec("layers/attn/wq", (4, 512, 512), mesh)
            print(json.dumps({"spec": str(s)}))
        """)
        assert "data" in out["spec"] and "model" in out["spec"]


class TestPipelineParallel:
    def test_pp_matches_sequential(self):
        """GPipe shard_map pipeline == sequential stage application, fwd and
        grad; boundary traffic equals Eq. 13."""
        out = run_py("""
            from repro.parallel.pipeline import pipeline_forward, pp_boundary_bytes
            from functools import partial

            S, m, mb, d = 4, 8, 2, 16
            mesh = make_mesh((S,), ("stage",))
            key = jax.random.PRNGKey(0)
            Ws = jax.random.normal(key, (S, d, d)) * 0.3

            def stage_fn(W, x):
                return jnp.tanh(x @ W)

            pipe = pipeline_forward(stage_fn, S, "stage")

            def run_pp(Ws, x):
                def inner(Wl, x):
                    return pipe(Wl[0], x)
                return jax.shard_map(inner, mesh=mesh,
                                 in_specs=(jax.sharding.PartitionSpec("stage"), jax.sharding.PartitionSpec()),
                                 out_specs=jax.sharding.PartitionSpec(),
                                 check_vma=False)(Ws, x)

            x = jax.random.normal(jax.random.PRNGKey(1), (m, mb, d))
            y_pp = run_pp(Ws, x)
            y_ref = x
            for i in range(S):
                y_ref = stage_fn(Ws[i], y_ref)

            err = float(jnp.max(jnp.abs(y_pp - y_ref)))

            # gradient flows through ppermute
            def loss_pp(Ws):
                return jnp.sum(run_pp(Ws, x) ** 2)
            def loss_ref(Ws):
                y = x
                for i in range(S):
                    y = stage_fn(Ws[i], y)
                return jnp.sum(y ** 2)
            g_pp = jax.grad(loss_pp)(Ws)
            g_ref = jax.grad(loss_ref)(Ws)
            gerr = float(jnp.max(jnp.abs(g_pp - g_ref)))
            vol = pp_boundary_bytes(mb, 1, d, m)
            print(json.dumps({"err": err, "gerr": gerr, "vol": vol}))
        """)
        assert out["err"] < 1e-5, out
        assert out["gerr"] < 1e-4, out
        assert out["vol"] == 2 * 2 * 1 * 16 * 8 * 2


class TestFlashDecoding:
    def test_seq_sharded_decode_matches_unsharded(self):
        """When KV heads don't divide the model axis, decode takes the
        flash-decoding path (seq-sharded partial attention).  Its logits
        must match the unsharded decode exactly."""
        out = run_py("""
            from repro.configs import get_config
            import dataclasses
            from repro.models import build_model, ModelOptions
            from repro.parallel import sharding as shd
            from repro.train.train_step import cache_shardings
            import numpy as np

            cfg = dataclasses.replace(
                get_config("glm4-9b").reduced(), n_heads=6, n_kv_heads=3,
                d_model=96, head_dim=16)
            model = build_model(cfg, ModelOptions(compute_dtype="float32",
                                                  remat=False))
            params = model.init(jax.random.PRNGKey(0))
            b, L = 4, 32
            toks = [jnp.full((b,1), t % cfg.vocab, jnp.int32) for t in range(5)]

            # reference: no mesh
            cache = model.init_cache(b, L)
            outs_ref = []
            for t in toks:
                lg, cache = jax.jit(model.decode_step)(params, cache, t)
                outs_ref.append(np.asarray(lg))

            # sharded: mesh (2,4); kv=3 % 4 != 0 -> seq-flash path
            mesh = make_mesh((2, 4), ("data", "model"))
            with shd.activate(mesh):
                p_sh = shd.param_shardings(jax.eval_shape(
                    lambda: model.init(jax.random.PRNGKey(0))), mesh)
                cache2 = model.init_cache(b, L)
                c_sh = cache_shardings(jax.eval_shape(
                    lambda: model.init_cache(b, L)), mesh, model=model)
                step = jax.jit(model.decode_step,
                               in_shardings=(p_sh, c_sh, None),
                               out_shardings=(None, c_sh))
                params2 = jax.device_put(params, p_sh)
                cache2 = jax.device_put(cache2, c_sh)
                outs_sh = []
                for t in toks:
                    lg, cache2 = step(params2, cache2, t)
                    outs_sh.append(np.asarray(lg))
            err = max(float(np.abs(a - b).max())
                      for a, b in zip(outs_ref, outs_sh))
            print(json.dumps({"err": err}))
        """)
        assert out["err"] < 1e-4, out


class TestCompressedCollectives:
    def test_compressed_psum_close_to_exact(self):
        out = run_py("""
            from repro.parallel.collectives import compressed_psum_mean
            mesh = make_mesh((8,), ("data",))
            x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

            def f(scheme):
                def inner(x):
                    return compressed_psum_mean(x[0], "data", scheme)
                return jax.shard_map(inner, mesh=mesh,
                                 in_specs=jax.sharding.PartitionSpec("data"),
                                 out_specs=jax.sharding.PartitionSpec(),
                                 check_vma=False)(x)
            exact = jnp.mean(x, 0)
            e16 = float(jnp.max(jnp.abs(f("fp16") - exact)))
            e8 = float(jnp.max(jnp.abs(f("int8") - exact)))
            print(json.dumps({"fp16": e16, "int8": e8}))
        """)
        assert out["fp16"] < 1e-2
        assert out["int8"] < 5e-2

    def test_error_feedback_unbiased(self):
        """Accumulated error feedback keeps the long-run mean of compressed
        grads equal to the true mean (within fp tolerance)."""
        out = run_py("""
            from repro.parallel.collectives import compress_with_feedback, init_error_feedback
            g = {"w": jnp.full((64,), 0.100048828125)}   # not fp16-representable
            res = init_error_feedback(g)
            total = jnp.zeros((64,))
            N = 64
            for _ in range(N):
                cg, res = compress_with_feedback(g, res, "int8")
                total = total + cg["w"]
            drift = float(jnp.max(jnp.abs(total / N - g["w"])))
            print(json.dumps({"drift": drift}))
        """)
        assert out["drift"] < 1e-3, out
