"""A second architecture, brought by the toy benchmark as files alone: the
rehearsal of ``archs/<name>.py``.  It is no model and carries no model's
name; it differs from the dense decoder where later configurations will:

- the first layer is of another kind than the rest (a dense GELU MLP, then
  layers that mix ``experts`` small MLPs by a softmax router);
- the experts' matrices are three-dimensional leaves ``[experts, in, out]``;
- the cache holds one latent of ``latent`` numbers a token and a layer, from
  which keys and values are expanded when they are attended to, so its leaf
  ``[B, slots, 1, latent]`` is not K or V of some heads;
- the counts are its own (a latent's bytes a token, every expert's
  matrices a token: the mix is dense), and hold only what its cells'
  readers call.

The program's side reuses the program's ``dot_attention`` and
``Attributes``; the plain reference is ``reference/latent_mix.py`` and the
counts are ``counts/latent_mix.py``, both of the toy's directory.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from benchmark import harness
from rocket_tpu.core.attributes import Attributes
from rocket_tpu.ops.attention import dot_attention

TOY = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = harness._load_module(TOY, "reference", "latent_mix")
counts = harness._load_module(TOY, "counts", "latent_mix")


def normalise(config: Dict) -> Dict:
    return dict(
        hidden=config["width"], layers=config["depth"],
        heads=config["heads"], head_dim=config["head_width"],
        latent=config["latent_width"], ffn=config["first_layer_ffn"],
        experts=config["experts"], expert_ffn=config["expert_ffn"],
        vocab=config["vocab_size"], vocab_padded=config["vocab_size"],
        max_pos=config["max_positions"], eps=config["norm_eps"])


def draft(arch: Dict, serving: Dict) -> Dict:
    """``draft_layers`` deep from the bottom: one layer is the dense one."""
    return dict(arch, layers=int(serving["draft_layers"]))


def leaf_shapes(arch: Dict, prefix: str = "") -> Dict[str, Tuple[int, ...]]:
    H, C, V = arch["hidden"], arch["latent"], arch["vocab_padded"]
    qk = arch["heads"] * arch["head_dim"]
    E, Fe = arch["experts"], arch["expert_ffn"]
    shapes: Dict[str, Tuple[int, ...]] = {"embed": (V, H)}
    for i in range(arch["layers"]):
        L = f"L{i}"
        shapes[f"{L}.ln1.scale"] = (H,)
        shapes[f"{L}.q.w"] = (H, qk)
        shapes[f"{L}.latent.w"] = (H, C)
        shapes[f"{L}.k.w"] = (C, qk)
        shapes[f"{L}.v.w"] = (C, qk)
        shapes[f"{L}.o.w"] = (qk, H)
        shapes[f"{L}.ln2.scale"] = (H,)
        if i == 0:
            shapes[f"{L}.up.w"] = (H, arch["ffn"])
            shapes[f"{L}.down.w"] = (arch["ffn"], H)
        else:
            shapes[f"{L}.router.w"] = (H, E)
            shapes[f"{L}.up.w"] = (E, H, Fe)
            shapes[f"{L}.down.w"] = (E, Fe, H)
    shapes["lnf.scale"] = (H,)
    shapes["head"] = (H, V)
    return {prefix + k: v for k, v in shapes.items()}


def leaf_name(path) -> str:
    """The program below names its leaves as the benchmark does."""
    keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
    return [k for k in keys if k != "value"][-1]


@dataclasses.dataclass(frozen=True)
class Config:
    arch: Tuple[Tuple[str, object], ...]
    max_seq: int
    decode_per_row: bool = False        # rows always keep their own frontier


class LatentMixLM(nn.Module):
    config: Config

    @nn.compact
    def __call__(self, batch, train: bool = False, decode: bool = False):
        a = dict(self.config.arch)
        shapes = leaf_shapes(a)
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (B, S))

        def leaf(name):
            init = (nn.initializers.ones if name.endswith(".scale")
                    else nn.initializers.normal(0.02))
            return self.param(name, init, shapes[name])

        def norm(x, name):
            x32 = x.astype(jnp.float32)
            var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
            y = x32 * jax.lax.rsqrt(var + a["eps"]) * leaf(name)
            return y.astype(x.dtype)

        x = leaf("embed")[tokens]
        for i in range(a["layers"]):
            L = f"L{i}"
            h = norm(x, f"{L}.ln1.scale")
            q = (h @ leaf(f"{L}.q.w")).reshape(
                B, S, a["heads"], a["head_dim"])
            latent = h @ leaf(f"{L}.latent.w")              # [B, S, C]
            q_offset = None
            if decode:
                filled = self.has_variable("cache", f"{L}.latent")
                held = self.variable(
                    "cache", f"{L}.latent", jnp.zeros,
                    (B, self.config.max_seq, 1, a["latent"]),
                    latent.dtype)
                if filled:
                    # one latent a token, written at each row's own
                    # frontier; slots past it are hidden causally
                    q_offset = positions[:, 0].astype(jnp.int32)
                    held.value = jax.vmap(
                        lambda c, u, s: jax.lax.dynamic_update_slice(
                            c, u, (s, 0, 0)))(
                        held.value, latent[:, :, None, :], q_offset)
                    latent = held.value[:, :, 0, :]         # [B, T, C]
            T = latent.shape[1]
            k = (latent @ leaf(f"{L}.k.w")).reshape(
                B, T, a["heads"], a["head_dim"])
            v = (latent @ leaf(f"{L}.v.w")).reshape(
                B, T, a["heads"], a["head_dim"])
            att = dot_attention(q, k, v, causal=True, q_offset=q_offset)
            x = x + att.reshape(B, S, -1) @ leaf(f"{L}.o.w")
            h = norm(x, f"{L}.ln2.scale")
            up, down = leaf(f"{L}.up.w"), leaf(f"{L}.down.w")
            if i == 0:
                x = x + nn.gelu(h @ up) @ down
            else:
                gates = jax.nn.softmax(
                    (h @ leaf(f"{L}.router.w")).astype(jnp.float32), -1)
                each = jnp.einsum(
                    "bsef,efh->bseh",
                    nn.gelu(jnp.einsum("bsh,ehf->bsef", h, up)), down)
                x = x + jnp.einsum("bse,bseh->bsh",
                                   gates.astype(x.dtype), each)
        out = Attributes(batch)
        out["logits"] = norm(x, "lnf.scale") @ leaf("head")
        return out


def program(arch: Dict, *, max_seq: int, attention: str = "auto"):
    """A flax module the trainer's ``Module`` and ``ContinuousBatcher``
    take as they are.  ``attention`` chooses among the program's kernels,
    of which this architecture uses the plain one alone."""
    return LatentMixLM(Config(arch=tuple(sorted(arch.items())),
                              max_seq=int(max_seq)))
