"""Requests of kind ``moe_router``: one batch of hidden states, routed on the
device by each MoE layer's router in turn and profiled as that layer's
expert-load count.

A request key is a number; its batch is made on the device from the seed
and the key, so a payload is rebuilt exactly for the check.  Each of the
``tokens_per_batch`` tokens is ``sqrt(common_share)`` times a unit
direction of the request, scaled to the RMS of 1, plus
``sqrt(1 - common_share)`` times i.i.d. N(0, 1), RMS-normalised (weight 1,
``rms_norm_eps``) and cast to bf16: the post-norm input a router sees.
The variants are the MoE layers ``first_k_dense_replace`` onwards, one
per ``num_hidden_layers``; each has a router weight at
``repro.models.moe``'s dense init and an ``e_score_correction_bias`` drawn
from N(0, ``bias_std``), both from the seed and placed on the device once,
at set-up.
"""

from __future__ import annotations

import functools

import numpy as np

from bench.traffic import seeded_rng


def moe_config(cfg: dict):
    """The program's ``MoEConfig`` from the published router keys."""
    from repro.models import moe  # lazy: the system under test

    return moe.MoEConfig(
        d_model=cfg["hidden_size"], d_expert=cfg["moe_intermediate_size"],
        num_experts=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["n_shared_experts"],
        scoring=cfg["scoring_func"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"])


def _jax_key(seed: int, *stream: int):
    import jax

    return jax.random.key(int(seeded_rng(seed, *stream).integers(0, 2**31)),
                          impl="rbg")


@functools.lru_cache(maxsize=None)
def _batch_fn(tokens: int, hidden: int, common_share: float, eps: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def hidden_batch(key):
        kd, kz = jax.random.split(key)
        d = jax.random.normal(kd, (hidden,), jnp.float32)
        d = d * jax.lax.rsqrt(jnp.mean(d * d))
        z = jax.random.normal(kz, (tokens, hidden), jnp.float32)
        x = np.sqrt(common_share) * d + np.sqrt(1.0 - common_share) * z
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        return x.astype(jnp.bfloat16)
    return hidden_batch


class Requests:
    def __init__(self, cfg: dict, request: dict, seed: int) -> None:
        import jax
        import jax.numpy as jnp

        from repro.models import moe  # lazy: the system under test

        self.cfg, self.request, self.seed = cfg, request, seed
        self.moe_cfg = moe_config(cfg)
        first = cfg["first_k_dense_replace"]
        self.variants = list(range(first, first + cfg["num_hidden_layers"]))
        self.routers = {}
        for layer in self.variants:
            r = moe.init_router(_jax_key(seed, 6, layer), self.moe_cfg)
            bias = request["bias_std"] * jax.random.normal(
                _jax_key(seed, 7, layer), (cfg["n_routed_experts"],),
                jnp.float32)
            self.routers[layer] = jax.device_put({"w": r["w"], "bias": bias})
        self._batch = _batch_fn(cfg["tokens_per_batch"], cfg["hidden_size"],
                                float(request["common_share"]),
                                float(cfg["rms_norm_eps"]))
        self._host: tuple = (None, None)

    def keys(self):
        i = 0
        while True:
            yield i
            i += 1

    def payload(self, key: int) -> dict:
        """The request's batch, made on the device; ``source`` hands the
        plain reference the same arrays on the host."""
        return {"key": key, "hidden": self._batch(_jax_key(self.seed, 5, key)),
                "source": self}

    def host_arrays(self, payload: dict, variant: int):
        """(hidden bf16, router weight, bias) of a request and layer as
        numpy arrays, read back off the clock; the last batch is kept."""
        if self._host[0] != payload["key"]:
            self._host = (payload["key"], np.asarray(payload["hidden"]))
        r = self.routers[variant]
        return self._host[1], np.asarray(r["w"]), np.asarray(r["bias"])

    def spec(self, payload: dict, variant: int, label: str):
        from repro.analysis import WorkloadSpec  # lazy: the system under test

        return WorkloadSpec.from_moe_router(
            {"router": self.routers[variant]}, payload["hidden"], self.moe_cfg,
            label=label, layer=variant,
            waves_per_tile=self.cfg["launch"]["waves_per_tile"])

    def launch(self, payload: dict) -> dict:
        cfg = self.cfg
        t, k = cfg["tokens_per_batch"], cfg["num_experts_per_tok"]
        return {"kernel": "scatter", "ids": t * k, "width": cfg["value_width"],
                "segments": cfg["n_routed_experts"],
                "commit_group": cfg["launch"]["commit_group"],
                "tokens": t, "hidden": cfg["hidden_size"], "top_k": k}
