"""Kind `topolm_prefill`: serving prefill of a MiniMax-Text-01 share through
the program's normal path, `api.init_cache` -> `api.prefill_into_cache`
(-> `api.decode_fn` for the check), under `launch.sharding.use_sharding`
on a mesh of data 1 x model <chips>, the weights placed by
`tree_param_specs`.

Set-up makes the weights and the prompt pools from the seed on the device
(the routers balanced, `topolm_prefill_ref.balance_routers`), compiles one
prefill program per call shape (lowered in turn, compiled side by side)
and runs each shape once. A call prefills one shape's prompts
into a fresh cache and brings the last-position logits to the host; the
window cycles the shapes in the seeded order. Two calls of the window,
drawn by a seeded reservoir sample, keep their caches: after the window
each runs the decode steps on seeded next ids, and the check compares
those calls' prefill and decode logits with `topolm_prefill_ref` on the
extended prompts, the reference taking the experts the program chose (its
route log, in the cache) and measuring how far they are from its own.
"""
from __future__ import annotations

import random
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import topolm_prefill_ref as ref
from lm_work import prefill_flops, lightning_sweep_work


def model_config(c: dict):
    """The program's `ModelConfig` for this configuration: the published
    MiniMax-Text-01 config at the configuration file's sizes, its layers
    one period of `attn_type_list`, the experts it holds the first
    `num_local_experts` of the published count."""
    from repro.configs.minimax_text_01 import CONFIG

    z = ref.sizes(c)
    period = tuple(z["kinds"])
    return CONFIG.replace(
        num_layers=len(period), num_superblocks=1, superblock=period,
        d_model=z["d"], num_heads=z["H"], num_kv_heads=z["KV"],
        head_dim=z["hd"], d_ff=z["ff"], moe_d_ff=z["ff"],
        vocab_size=z["V"], num_experts=z["E"], top_k=z["K"],
        moe_held=tuple(range(z["held"])), lightning_decay_layers=z["N"],
        rope_theta=z["theta"], rotary_dim=z["rd"], norm_eps=z["eps"],
        residual_alpha=z["alpha"], residual_beta=z["beta"],
        dtype=c["dtype"], remat=False)


def weights(seed: int, c: dict, traffic: dict):
    """The seed's weights, placed by `tree_param_specs` on the current mesh,
    their routers balanced on one block of the reference's rows."""
    import jax
    import jax.numpy as jnp
    from repro.launch import sharding as SH

    shapes_tree = jax.eval_shape(
        lambda: ref.init_params(seed, c, jnp.bfloat16))
    shardings = jax.tree.map(SH.named_sharding,
                             SH.tree_param_specs(shapes_tree))
    params = ref.init_params(seed, c, jnp.dtype(c["dtype"]), shardings)
    return ref.balance_routers(params, c, seed, traffic["ref_rows"])


def prefill(cfg, S):
    """(params, tokens (B, L)) -> (last logits (B, V), filled cache)."""
    import jax.numpy as jnp
    from repro.models import api

    def fn(params, tokens):
        B, L = tokens.shape
        cache = api.init_cache(cfg, B, S)
        lengths = jnp.full((B,), L, jnp.int32)
        return api.prefill_into_cache(cfg, params, cache, tokens, lengths, S)

    return fn


def decode(cfg, S):
    from repro.models import api

    def fn(params, cache, token, pos):
        logits, cache = api.decode_fn(cfg, params, cache, token, pos, S)
        return logits[:, 0], cache

    return fn


class PrefillCell:
    def __init__(self, config, traffic, seed, host, memo):
        import jax
        import jax.numpy as jnp
        from repro.launch.mesh import make_mesh

        memo = {} if memo is None else memo
        self.config, self.traffic, self.seed = config, traffic, seed
        self.cfg = cfg = model_config(config)
        n = traffic["decode_steps"]
        shapes = [tuple(s) for s in traffic["shapes"]]
        model = min(config["mesh"]["model"], len(jax.devices()))
        if "mesh" not in memo:
            memo["mesh"] = make_mesh((1, model), ("data", "model"))
        self.mesh = memo["mesh"]
        with self.sharding(), host.span("prepare"):
            self.params = weights(seed, config, traffic)
            pool = traffic["pool"]
            self.prompts = {s: [jnp.asarray(p) for p in
                                ref.prompts(seed, config, s, pool)]
                            for s in shapes}
            jax.block_until_ready((self.params, self.prompts))
        self.order = ref.call_order(seed, shapes)
        key = ("compiled", tuple(shapes))
        with self.sharding(), host.span("compile", timed=True):
            if key not in memo:
                lowered = [jax.jit(prefill(cfg, L + n)).lower(
                    self.params, jax.ShapeDtypeStruct((B, L), jnp.int32))
                    for B, L in shapes]
                with ThreadPoolExecutor(len(shapes)) as pool:
                    memo[key] = dict(zip(shapes, pool.map(
                        lambda lo: lo.compile(), lowered)))
            self.progs = memo[key]
        self.decode = {}
        with self.sharding(), host.span("warmup"):
            for s in shapes:
                logits, _ = self.progs[s](self.params, self.prompts[s][0])
                np.asarray(logits)
        self.k = 0
        self.nonfinite = 0
        self.kept: list = []  # (call, shape, pool index, logits, cache)
        self.pick = random.Random(seed)
        self.work = {"call_flops": [],
                     "sweep": lightning_sweep_work(config, shapes[0]),
                     "chips": model}

    def sharding(self):
        from repro.launch import sharding as SH

        return SH.use_sharding(self.mesh)

    def _call(self, host):
        s = self.order[self.k % len(self.order)]
        i = (self.k // len(self.order)) % len(self.prompts[s])
        with host.span("dispatch"):
            logits, cache = self.progs[s](self.params, self.prompts[s][i])
        with host.span("wait"):
            out = np.asarray(logits)
        return s, i, out, cache

    def step(self, host) -> bool:
        s, i, out, cache = self._call(host)
        ok = bool(np.isfinite(out).all())
        self.nonfinite += not ok
        # a seeded reservoir sample of 2 calls keeps its caches
        n_keep = self.traffic["check_calls"]
        entry = (self.k, s, i, out, cache)
        if len(self.kept) < n_keep:
            self.kept.append(entry)
        else:
            j = self.pick.randrange(self.k + 1)
            if j < n_keep:
                self.kept[j] = entry
        self.work["call_flops"].append(prefill_flops(self.config, s))
        self.k += 1
        return ok

    def after_window(self) -> int:
        """The kept calls' decode steps through their filled caches."""
        import jax
        import jax.numpy as jnp

        n = self.traffic["decode_steps"]
        self.checked = []
        with self.sharding():
            for call, s, i, out, cache in self.kept:
                B, L = s
                if s not in self.decode:
                    self.decode[s] = jax.jit(decode(self.cfg, L + n),
                                             donate_argnums=(1,))
                ids = ref.next_ids(self.seed, self.config, call, B, n)
                steps = [out]
                for t in range(n):
                    lg, cache = self.decode[s](
                        self.params, cache, jnp.asarray(ids[:, t:t + 1]),
                        jnp.asarray(L + t, jnp.int32))
                    steps.append(np.asarray(lg))
                self.checked.append((call, s, i, ids, np.stack(steps, 1),
                                     route_log(self.config, cache)))
                del cache
        self.kept = []
        return 0

    def free(self):
        del self.progs, self.decode, self.prompts

    def check(self, limits: dict) -> dict:
        n = self.traffic["decode_steps"]
        V = self.config["vocab_size"]
        reference = ref.Reference(self.config, rows=self.traffic["ref_rows"],
                                  max_len=max_len(self.traffic))
        lg, dg, rg = 0.0, 0.0, 0.0
        with self.sharding():
            for call, s, i, ids, got, routes in self.checked:
                toks = np.concatenate(
                    [ref.prompts(self.seed, self.config, s,
                                 self.traffic["pool"])[i], ids], axis=1)
                want, _, route_gap = reference.forward(self.params, toks,
                                                       n + 1, routes)
                lg = max(lg, ref.gap(got[:, 0, :V], want[:, 0]))
                dg = max(dg, ref.gap(got[:, 1:, :V], want[:, 1:]))
                rg = max(rg, route_gap)
        del self.params
        nums = {"logit_gap": lg, "decode_gap": dg, "route_gap": rg,
                "nonfinite_calls": float(self.nonfinite)}
        return {k: {"value": nums[k], "limit": v} for k, v in limits.items()}


def max_len(traffic: dict) -> int:
    """Tokens of the longest sequence the check compares."""
    return max(L for _, L in traffic["shapes"]) + traffic["decode_steps"]


def route_log(c: dict, cache) -> list:
    """The program's route log of a filled cache, [row][layer] (S, k): the
    experts each position was routed to (`lm._log_routes`)."""
    kinds = ref.sizes(c)["kinds"]
    logs = [np.asarray(cache["blocks0"][ref.block_key(l, kind)]["routes"][0])
            for l, kind in enumerate(kinds)]
    return [[log[b] for log in logs] for b in range(logs[0].shape[0])]


def setup(config, traffic, seed, host, memo=None):
    return PrefillCell(config, traffic, seed, host, memo)


def control(config, traffic, seed, memo=None, variant: str = "fp8") -> dict:
    """The numbers `correct` compares, read off a control in the program's
    place: the reference with every matmul's inputs in float8 e4m3 (`fp8`),
    with no lightning decay (`no_decay`), or with the lightning sum
    normalized (`normalized`), its logits and its own expert choices set
    against the reference's, on the first call of the seed's order and its
    decode ids."""
    import jax
    from repro.launch import sharding as SH
    from repro.launch.mesh import make_mesh

    memo = {} if memo is None else memo
    n = traffic["decode_steps"]
    model = min(config["mesh"]["model"], len(jax.devices()))
    if "mesh" not in memo:
        memo["mesh"] = make_mesh((1, model), ("data", "model"))
    with SH.use_sharding(memo["mesh"]):
        params = weights(seed, config, traffic)
        s = ref.call_order(seed, [tuple(x) for x in traffic["shapes"]])[0]
        toks = np.concatenate([ref.prompts(seed, config, s,
                                           traffic["pool"])[0],
                               ref.next_ids(seed, config, 0, s[0], n)], 1)
        kw = dict(rows=traffic["ref_rows"], max_len=max_len(traffic))
        got, routes, _ = ref.Reference(config, variant, **kw).forward(
            params, toks, n + 1)
        want, _, route_gap = ref.Reference(config, **kw).forward(
            params, toks, n + 1, routes)
        del params
    return {"logit_gap": ref.gap(got[:, 0], want[:, 0]),
            "decode_gap": ref.gap(got[:, 1:], want[:, 1:]),
            "route_gap": route_gap,
            "nonfinite_calls": float(not np.isfinite(got).all())}
