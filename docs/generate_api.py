"""API-doc generator — markdown from docstrings.

Capability parity: reference ``docs/create_api_md.py:5-39`` generates one
``.md`` per public class (driven by ``rocket/core/__init__.py``'s
``__sphinx_classes__`` list) for a Sphinx/furo site.  Here the same idea
with zero extra dependencies: walk the public package surface, emit
GitHub-renderable markdown straight from signatures + docstrings into
``docs/api/``.

Run: ``python docs/generate_api.py`` (writes ``docs/api/*.md`` + index).
"""

from __future__ import annotations

import importlib
import inspect
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "docs", "api")

# module -> one-line section description (the curated public surface;
# rocket_tpu/__init__.py flattens most of these to `rocket_tpu.*`).
MODULES = {
    "rocket_tpu.core.attributes": "Attributes blackboard",
    "rocket_tpu.core.events": "Lifecycle events",
    "rocket_tpu.core.capsule": "Capsule base protocol",
    "rocket_tpu.core.dispatcher": "Composite dispatch",
    "rocket_tpu.core.module": "Compute capsule (jitted train step)",
    "rocket_tpu.core.loss": "Loss capsule",
    "rocket_tpu.core.optimizer": "Optimizer capsule",
    "rocket_tpu.core.scheduler": "LR scheduler capsule",
    "rocket_tpu.runtime": "Runtime (mesh, policy, registries)",
    "rocket_tpu.launch.launcher": "Launcher (epoch loop, resume)",
    "rocket_tpu.launch.loop": "Looper (iteration loop)",
    "rocket_tpu.launch.notebook": "Notebook / interactive launch",
    "rocket_tpu.data.dataset": "Dataset capsule",
    "rocket_tpu.data.loader": "Data loader (per-host sharded, streaming)",
    "rocket_tpu.data.source": "Data sources (map-style + streaming)",
    "rocket_tpu.parallel.pipeline": "GPipe pipeline parallelism",
    "rocket_tpu.models.moe": "Mixture-of-Experts (expert parallel)",
    "rocket_tpu.models.seq2seq": "Encoder-decoder (T5-style) family",
    "rocket_tpu.engine.state": "TrainState pytree",
    "rocket_tpu.engine.ema": "Parameter EMA (optax transform)",
    "rocket_tpu.engine.step": "Jitted step builders",
    "rocket_tpu.engine.precision": "Mixed-precision policy",
    "rocket_tpu.engine.adapter": "Model adapters",
    "rocket_tpu.parallel.mesh": "Device mesh construction",
    "rocket_tpu.parallel.sharding": "Sharding rules",
    "rocket_tpu.parallel.collectives": "Collective ops (NCCL-surface map)",
    "rocket_tpu.parallel.multihost": "Host-level coordination (DCN)",
    "rocket_tpu.ops.attention": "Attention dispatch",
    "rocket_tpu.ops.flash": "Pallas flash attention (TPU kernel)",
    "rocket_tpu.ops.decode_attention": "Pallas decode attention over a KV cache (TPU kernel)",
    "rocket_tpu.ops.latent_attention":
        "Pallas absorbed latent attention over its cache (TPU kernel)",
    "rocket_tpu.ops.select_attention":
        "Attention that chooses its keys (indexer, top-k, gather or mask)",
    "rocket_tpu.ops.ssm":
        "Selective scan (chunked and step forms) and the round's state "
        "update (TPU kernel)",
    "rocket_tpu.ops.fused_ce": "Fused logits-free linear cross-entropy",
    "rocket_tpu.ops.ring": "Ring attention (sequence parallel)",
    "rocket_tpu.ops.quant": "Int8 weight-only quantization (W8A16 decode)",
    "rocket_tpu.observe.meter": "Meter / Metric (distributed eval metrics)",
    "rocket_tpu.observe.tracker": "Tracker + ImageLogger",
    "rocket_tpu.observe.backends": "Tracker backends",
    "rocket_tpu.observe.profile": "Profiler / Throughput / debug mode",
    "rocket_tpu.persist.checkpoint": "Checkpointer capsule",
    "rocket_tpu.persist.orbax_io": "Orbax checkpoint IO",
    "rocket_tpu.models.transformer": "Transformer LM family",
    "rocket_tpu.models.mamba": "State-space (Mamba-2) mixer and its cache",
    "rocket_tpu.models.resnet": "ResNet family",
    "rocket_tpu.models.vit": "ViT family",
    "rocket_tpu.models.lenet": "LeNet (MNIST example model)",
    "rocket_tpu.models.lora": "LoRA utilities",
    "rocket_tpu.models.generate": "Autoregressive generation (KV-cache decode, beam search)",
    "rocket_tpu.models.objectives": "Stock objectives",
    "rocket_tpu.utils.placement": "Collate + device placement",
    "rocket_tpu.utils.collections": "Pytree helpers",
    "rocket_tpu.utils.logging": "Rank-aware logging",
}


def _signature(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # Default-value reprs of functions/objects embed memory addresses
    # ("<function adamw at 0x7f..>"), which would churn every page on every
    # regeneration — strip them so output is deterministic.
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def _doc(obj) -> str:
    # flax dataclass docstrings embed the constructor signature, sentinel
    # reprs and all — strip addresses here too (see _signature).
    return re.sub(r" at 0x[0-9a-f]+", "", inspect.getdoc(obj) or "")


def _public_members(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in vars(mod) if not n.startswith("_")]
    out = []
    for name in names:
        obj = getattr(mod, name, None)
        if obj is None or inspect.ismodule(obj):
            continue
        if getattr(obj, "__module__", None) != mod.__name__:
            continue  # re-exports documented at their home module
        if inspect.isclass(obj) or inspect.isfunction(obj):
            out.append((name, obj))
    return out


def _render_class(name: str, cls) -> list:
    lines = [f"### `{name}{_signature(cls)}`", ""]
    doc = _doc(cls)
    if doc:
        lines += [doc, ""]
    for mname, member in sorted(vars(cls).items()):
        if mname.startswith("_") or not inspect.isfunction(member):
            continue
        mdoc = _doc(member)
        if not mdoc:
            continue
        lines += [f"#### `{name}.{mname}{_signature(member)}`", "", mdoc, ""]
    return lines


def _render_module(modname: str, title: str) -> str:
    mod = importlib.import_module(modname)
    lines = [f"# `{modname}` — {title}", ""]
    doc = _doc(mod)
    if doc:
        lines += [doc, ""]
    for name, obj in _public_members(mod):
        if inspect.isclass(obj):
            lines += _render_class(name, obj)
        else:
            lines += [f"### `{name}{_signature(obj)}`", ""]
            fdoc = _doc(obj)
            if fdoc:
                lines += [fdoc, ""]
    return "\n".join(lines).rstrip() + "\n"


def main() -> None:
    os.makedirs(OUT, exist_ok=True)
    index = [
        "# rocket_tpu API reference",
        "",
        "Generated by `python docs/generate_api.py` from docstrings",
        "(the reference's `docs/create_api_md.py` equivalent).",
        "",
    ]
    for modname, title in MODULES.items():
        fname = modname.replace(".", "_") + ".md"
        with open(os.path.join(OUT, fname), "w") as fh:
            fh.write(_render_module(modname, title))
        index.append(f"- [`{modname}`]({fname}) — {title}")
    with open(os.path.join(OUT, "README.md"), "w") as fh:
        fh.write("\n".join(index) + "\n")
    print(f"wrote {len(MODULES)} module pages + index to {OUT}")


if __name__ == "__main__":
    main()
