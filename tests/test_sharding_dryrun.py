"""Sharding rules + a miniature end-to-end dry-run (subprocess, 8 devices)."""
import pytest


def test_param_rules_basics(subprocess_py):
    out = subprocess_py("""
        import jax
        from jax.sharding import PartitionSpec as P
        AUTO = (jax.sharding.AxisType.Auto,) * 2
        from repro.models.partitioning import make_rules, param_partition_spec

        mesh = jax.make_mesh((4, 2), ('data', 'model'), axis_types=AUTO)
        rules = make_rules(mesh)
        # generic matmul weight: in->dp, out->model
        assert param_partition_spec('blocks/attn/wq', (8, 64, 64), rules) == \\
            P(None, ('data',), ('model',))
        # output projection transposes
        assert param_partition_spec('blocks/mlp/wo_mlp', (8, 64, 64), rules) == \\
            P(None, ('model',), ('data',))
        # embed: vocab->model, d->dp
        assert param_partition_spec('embedding/embed', (1000, 64), rules) == \\
            P(('model',), ('data',))
        # expert stack with E divisible -> EP
        assert param_partition_spec('blocks/moe/we_in', (8, 4, 64, 32), rules) == \\
            P(None, ('model',), ('data',), None)
        # expert stack with E NOT divisible -> TP over d_out
        assert param_partition_spec('blocks/moe/we_in', (8, 3, 64, 32), rules) == \\
            P(None, None, ('data',), ('model',))
        # norm scales replicate
        assert param_partition_spec('blocks/ln1/scale', (8, 64), rules) == P()
        # non-divisible dims are dropped (whisper vocab 51865)
        assert param_partition_spec('embedding/embed', (51865, 64), rules) == \\
            P(None, ('data',))
        print('RULES_OK')
    """, devices=8)
    assert "RULES_OK" in out


def test_mini_dryrun_train_and_decode(subprocess_py):
    """Full dry-run machinery on an 8-device host mesh with a reduced arch."""
    out = subprocess_py("""
        import dataclasses, jax
        AUTO = (jax.sharding.AxisType.Auto,) * 2
        from repro.configs import get_config
        from repro.core import MethodConfig
        from repro.launch.sharding import (batch_spec_tree, cache_spec_tree,
                                           state_spec_tree, to_named)
        from repro.launch.steps import (make_decode_step, make_train_setup)
        from repro.models import build_model, batch_spec, decode_batch_spec
        from repro.models.config import ShapeSpec
        from repro.models.partitioning import activation_sharding

        cfg = get_config('olmo-1b', reduced=True)
        bundle = build_model(cfg)
        mesh = jax.make_mesh((4, 2), ('data', 'model'), axis_types=AUTO)
        shape = ShapeSpec('mini_train', 'train', 64, 8)

        with jax.set_mesh(mesh), activation_sharding(mesh):
            setup = make_train_setup(bundle, MethodConfig(n_microbatches=2))
            state_sds = jax.eval_shape(lambda: setup.init_state(
                bundle.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1)))
            batch_sds = batch_spec(cfg, shape, ascent_fraction=0.25)
            state_sh = to_named(state_spec_tree(state_sds, cfg, mesh), mesh)
            batch_sh = to_named(batch_spec_tree(batch_sds, mesh), mesh)
            c = jax.jit(setup.step_fn, in_shardings=(state_sh, batch_sh),
                        out_shardings=(state_sh, None), donate_argnums=(0,)
                        ).lower(state_sds, batch_sds).compile()
            assert c.cost_analysis()['flops'] > 0
            print('TRAIN_COMPILED', int(c.memory_analysis().temp_size_in_bytes > 0))

            dshape = ShapeSpec('mini_decode', 'decode', 64, 8)
            step = make_decode_step(bundle)
            params_sds = jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0)))
            cache_sds = jax.eval_shape(lambda: bundle.init_cache(8, 64, pos=63))
            dbatch_sds = decode_batch_spec(cfg, dshape)
            params_sh = to_named(state_spec_tree(params_sds, cfg, mesh), mesh)
            cache_sh = to_named(cache_spec_tree(cache_sds, cfg, mesh), mesh)
            dbatch_sh = to_named(batch_spec_tree(dbatch_sds, mesh), mesh)
            c2 = jax.jit(step, in_shardings=(params_sh, cache_sh, dbatch_sh),
                         out_shardings=(None, cache_sh), donate_argnums=(1,)
                         ).lower(params_sds, cache_sds, dbatch_sds).compile()
            print('DECODE_COMPILED')
    """, devices=8)
    assert "TRAIN_COMPILED 1" in out
    assert "DECODE_COMPILED" in out


def test_sharded_training_matches_single_device(subprocess_py):
    """pjit-sharded AsyncSAM training equals unsharded training bit-for-bit
    (up to float summation order) on the same data."""
    out = subprocess_py("""
        import jax, jax.numpy as jnp
        AUTO = (jax.sharding.AxisType.Auto,) * 2
        from repro.configs import get_config
        from repro.core import MethodConfig, make_method, init_train_state
        from repro import optim
        from repro.models import build_model, synth_batch
        from repro.launch.sharding import state_spec_tree, to_named
        from repro.models.partitioning import activation_sharding

        cfg = get_config('olmo-1b', reduced=True)
        bundle = build_model(cfg)
        mcfg = MethodConfig(name='async_sam', rho=0.02, ascent_fraction=0.5)
        method = make_method(mcfg)
        opt = optim.sgd(1e-2, momentum=0.9)
        params = bundle.init(jax.random.PRNGKey(0))
        batches = [synth_batch(cfg, 8, 16, jax.random.PRNGKey(i), 0.5)
                   for i in range(4)]

        def run(sharded):
            state = init_train_state(params, opt, method, jax.random.PRNGKey(1))
            step = method.make_step(bundle.loss_fn, opt)
            if sharded:
                mesh = jax.make_mesh((4, 2), ('data', 'model'), axis_types=AUTO)
                with jax.set_mesh(mesh), activation_sharding(mesh):
                    sh = to_named(state_spec_tree(
                        jax.eval_shape(lambda: state), cfg, mesh), mesh)
                    state = jax.device_put(state, sh)
                    jstep = jax.jit(step, out_shardings=(sh, None))
                    for b in batches:
                        state, m = jstep(state, b)
            else:
                jstep = jax.jit(step)
                for b in batches:
                    state, m = jstep(state, b)
            return jax.device_get(state.params), float(m['loss'])

        p1, l1 = run(False)
        p8, l8 = run(True)
        import numpy as np
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p8)))
        print('MAXERR', err, 'LOSSDIFF', abs(l1 - l8))
        assert err < 5e-4, err
        assert abs(l1 - l8) < 1e-3
    """, devices=8)
    assert "MAXERR" in out


def test_production_dryrun_cell_subprocess(subprocess_py):
    """The real 512-device production dry-run for one cheap cell."""
    out = subprocess_py("""
        from repro.launch.dryrun import run_cell
        r = run_cell('whisper-tiny', 'decode_32k', save=False, verbose=False)
        assert r.status == 'ok', r.note
        assert r.peak_memory_per_device < 16e9
        print('CELL_OK', r.n_collectives > 0)
    """, devices=512, timeout=560)
    assert "CELL_OK" in out
