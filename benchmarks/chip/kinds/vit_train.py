"""Kind `vit_train`: TopoViT training steps through the program's
`vit.forward` (Alg. 1 on the grid plan) and `adamw_update`, in the
configuration's dtype, each step's batch split into microbatches whose
gradients are averaged.

Set-up makes the weights and a pool of batches from the seed on the
device, compiles the step (donating the weights and the optimizer state),
and drives that compiled step through the first `check_steps` steps on
batches 0, 1, 2, ...: those steps are the ones the check compares with
`vit_train_ref`, and the same step and state then run the window, which
goes on cycling the pool.
"""
from __future__ import annotations

import numpy as np

import vit_train_ref as ref
from work import vit_train_flops_per_image

# the configuration keys that are the program's `ModelConfig` fields
MODEL_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "num_prefix_embeddings", "performer_phi",
              "topo_g", "topo_degree", "topo_dist_scale", "mlp_act",
              "norm_eps", "dtype", "attention_variant", "topo_synced",
              "remat")


def model_config(c: dict):
    """The program's `ModelConfig` for this configuration: its published
    TopoViT-B/16 config with the sizes of the configuration file."""
    from repro.configs.topovit_b16 import CONFIG

    return CONFIG.replace(**{k: c[k] for k in MODEL_KEYS})


def loss(cfg, integ, params, patches, labels):
    """Mean softmax cross-entropy of `vit.forward` (float32 logits)."""
    import jax
    import jax.numpy as jnp
    from repro.models import vit

    logits = vit.forward(cfg, params, patches, integ).astype(jnp.float32)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=1) - picked)


def make_step(cfg, integ, opt_cfg, microbatches: int):
    """The train step: gradients of `loss` averaged over `microbatches`
    slices of the batch by a scan, cast to the weights' dtype, then one
    `adamw_update`. Returns (params, opt_state, loss)."""
    import jax
    import jax.numpy as jnp
    from repro.optim import adamw

    grad_fn = jax.value_and_grad(
        lambda p, x, y: loss(cfg, integ, p, x, y))

    def step(params, opt_state, patches, labels):
        def split(a):
            return a.reshape((microbatches, -1) + a.shape[1:])

        def acc(carry, mb):
            lv, grads = grad_fn(params, *mb)
            return jax.tree.map(jnp.add, carry, (lv, grads)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params))
        (lv, grads), _ = jax.lax.scan(
            acc, zero, (split(patches), split(labels)))
        lv = lv / microbatches
        grads = jax.tree.map(lambda g, p: (g / microbatches).astype(p.dtype),
                             grads, params)
        params, opt_state, _ = adamw.adamw_update(grads, opt_state, params,
                                                  opt_cfg)
        return params, opt_state, lv

    return step


class TrainCell:
    def __init__(self, config, traffic, seed, host, memo):
        import jax
        import jax.numpy as jnp
        from repro.models import vit
        from repro.optim import adamw

        self.config, self.traffic, self.seed = config, traffic, seed
        cfg = model_config(config)
        o = config["optimizer"]
        self.opt = o
        B, mb, pool = config["batch"], config["microbatches"], \
            traffic["pool"]
        steps = traffic["check_steps"]
        if pool <= steps:
            raise ValueError("the pool must outnumber the checked steps, so "
                             "that they run on batches that all differ")
        opt_cfg = adamw.AdamWConfig(**{k: o[k] for k in (
            "lr", "b1", "b2", "eps", "weight_decay", "warmup_steps",
            "total_steps", "min_lr_ratio", "clip_norm")})
        with host.span("prepare"):
            integ = vit.build_grid_integrator(cfg)
            params = ref.init_params(seed, config, jnp.dtype(config["dtype"]))
            opt_state = adamw.adamw_init(params)
            self.patches, self.labels = ref.make_batches(seed, config, pool,
                                                         B)
            self.batches = [(self.patches[i], self.labels[i])
                            for i in range(pool)]
            jax.block_until_ready((params, opt_state, self.batches))
        step = jax.jit(make_step(cfg, integ, opt_cfg, mb),
                       donate_argnums=(0, 1))
        norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32)))) for a in jax.tree.leaves(t)])
        diff = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b))
        with host.span("compile", timed=True):
            self.step_c = step.lower(params, opt_state,
                                     *self.batches[0]).compile()
            norms_c = norms.lower(opt_state.mu).compile()
            diff_c = diff.lower(params, params).compile()
        # the first steps, through the window's own step and feed
        with host.span("warmup"):
            p0 = jax.tree.map(jnp.copy, params)
            losses = []
            for s in range(steps):
                params, opt_state, lv = self.step_c(params, opt_state,
                                                    *self.batches[s])
                losses.append(float(lv))
                if s == 0:
                    g1 = np.asarray(jax.device_get(norms_c(opt_state.mu)),
                                    np.float64) / (1.0 - o["b1"])
            delta = np.asarray(jax.device_get(norms_c(diff_c(params, p0))),
                               np.float64)
            del p0
        self.prog = {"losses": losses, "grad_norms": g1,
                     "change_norms": delta}
        self.params, self.opt_state = params, opt_state
        self.k = steps
        self.work = {"images_per_call": B,
                     "flops_per_image": vit_train_flops_per_image(config)}

    def step(self, host) -> bool:
        x, y = self.batches[self.k % len(self.batches)]
        with host.span("dispatch"):
            self.params, self.opt_state, lv = self.step_c(
                self.params, self.opt_state, x, y)
        with host.span("wait"):
            lv = float(lv)
        self.k += 1
        return bool(np.isfinite(lv))

    def after_window(self) -> int:
        """Every step's loss was read and checked in the window."""
        return 0

    def free(self):
        del self.params, self.opt_state, self.step_c, self.batches
        del self.patches, self.labels

    def check(self, limits: dict) -> dict:
        t = self.traffic
        r = ref.reference_run(self.config, self.opt, self.seed, t["pool"],
                              self.config["batch"], t["check_steps"])
        nums = ref.compare(self.prog, r)
        return {k: {"value": nums[k], "limit": v} for k, v in limits.items()}


def setup(config, traffic, seed, host, memo=None):
    return TrainCell(config, traffic, seed, host, memo)


def control(config, traffic, seed, memo=None) -> dict:
    """The numbers `correct` compares, read off the control (the reference
    with every matmul's inputs rounded to float8 e4m3) against the
    reference, on the steps a run with this seed compares."""
    t = traffic
    args = (config, config["optimizer"], seed, t["pool"], config["batch"],
            t["check_steps"])
    return ref.compare(ref.reference_run(*args, rounding="fp8"),
                       ref.reference_run(*args))
