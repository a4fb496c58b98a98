"""Architecture ``decoder``: dense decoder-only transformers in two dialects
of configuration file, GPT-2's (``n_embd`` ...: learned positions, LayerNorm,
GELU, biases, tied head) and the ``hidden_size`` dialect of Llama-like
configurations (RoPE, RMSNorm, SwiGLU, grouped-query attention, a sliding
window, an untied head).

A configuration file names its architecture under ``reference``; the harness
loads ``archs/<reference>.py`` and everything that depends on the shape of
the model goes through the seven names a module here gives:

- ``normalise(config) -> arch``: the sizes in the benchmark's own keys;
- ``draft(arch, serving) -> arch``: the draft a speculative server needs;
- ``program(arch, *, max_seq, attention)``: the program's flax module;
- ``leaf_shapes(arch, prefix)`` and ``leaf_name(path)``: the benchmark's
  name and shape of every leaf, and the name of a leaf of the program's tree;
- ``reference``: the plain reference (``loss_and_grads``, ``train_steps``,
  ``served_logits``);
- ``counts``: operations and bytes from shapes alone, under the names the
  cell's readers ask for (``counts/decoder.py``).
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark import harness
from benchmark.counts import decoder as counts  # noqa: F401
from benchmark.reference import decoder as reference  # noqa: F401


def normalise(config: Dict) -> Dict:
    """The sizes the counts, the weights and the reference need, from a
    configuration file written in its source's own keys (GPT-2's
    ``n_embd`` dialect or the ``hidden_size`` dialect of Llama-like
    configs).  A key the file lacks is an error."""
    assumed = config.get("assumed", {})
    if "n_embd" in config:
        hidden, heads = config["n_embd"], config["n_head"]
        arch = dict(
            hidden=hidden, layers=config["n_layer"], heads=heads,
            kv_heads=heads, head_dim=hidden // heads,
            ffn=config.get("n_inner") or 4 * hidden,
            vocab=config["vocab_size"], max_pos=config["n_positions"],
            norm="layernorm", mlp="gelu", positions="learned", bias=True,
            tie=True, window=None, rope_theta=None,
            eps=config["layer_norm_epsilon"])
    elif "hidden_size" in config:
        hidden, heads = config["hidden_size"], config["num_attention_heads"]
        arch = dict(
            hidden=hidden, layers=config["num_hidden_layers"], heads=heads,
            kv_heads=config.get("num_key_value_heads", heads),
            head_dim=config.get("head_dim") or hidden // heads,
            ffn=config["intermediate_size"], vocab=config["vocab_size"],
            max_pos=config["max_position_embeddings"], norm="rmsnorm",
            mlp="swiglu", positions="rope", bias=False,
            tie=bool(config.get("tie_word_embeddings", False)),
            window=config.get("sliding_window"),
            rope_theta=config["rope_theta"], eps=config["rms_norm_eps"])
    else:
        raise harness.BenchmarkError(
            "configuration file is in no dialect the decoder architecture "
            "knows (n_embd / hidden_size)")
    arch["vocab_padded"] = int(assumed.get("vocab_padded_to", arch["vocab"]))
    return arch


def draft(arch: Dict, serving: Dict) -> Dict:
    """The draft a speculative server needs: the same widths, its own
    embeddings and head, ``draft_layers`` deep."""
    return dict(arch, layers=int(serving["draft_layers"]))


def program(arch: Dict, *, max_seq: int, attention: str = "auto"):
    """The program's ``TransformerLM`` for this architecture."""
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM

    return TransformerLM(TransformerConfig(
        vocab_size=arch["vocab_padded"], hidden=arch["hidden"],
        n_layers=arch["layers"], n_heads=arch["heads"],
        n_kv_heads=arch["kv_heads"], ffn_dim=arch["ffn"],
        max_seq=int(max_seq), norm=arch["norm"],
        mlp=arch["mlp"], positions=arch["positions"],
        rope_theta=arch["rope_theta"] or 10000.0,
        tie_embeddings=arch["tie"], use_bias=arch["bias"],
        norm_eps=arch["eps"], attention=attention,
        attention_window=arch["window"]))


def leaf_name(path) -> str:
    """The benchmark's name for a leaf of the program's parameter tree:
    ``block_3/attn/q/kernel`` -> ``L3.q.w``."""
    keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
    keys = [k for k in keys if k != "value"
            and not k.startswith(("LayerNorm_", "RMSNorm_"))]
    kind = {"kernel": "w", "bias": "b", "scale": "scale"}
    if keys[0] == "embed":
        return "embed"
    if keys[0] == "pos_embedding":
        return "pos"
    if keys[0] == "head":
        return "head"
    if keys[0] == "ln_f":
        return "lnf." + ("bias" if keys[-1] == "bias" else "scale")
    if keys[0].startswith("block_"):
        layer = f"L{keys[0][len('block_'):]}"
        if keys[1] in ("ln1", "ln2"):
            return f"{layer}.{keys[1]}." + (
                "bias" if keys[-1] == "bias" else "scale")
        return f"{layer}.{keys[2]}.{kind[keys[-1]]}"
    raise harness.BenchmarkError(f"no name for program leaf {keys}")


def leaf_shapes(arch: Dict, prefix: str = "") -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of every leaf of a decoder of this architecture."""
    H, F, V = arch["hidden"], arch["ffn"], arch["vocab_padded"]
    q_dim = arch["heads"] * arch["head_dim"]
    kv_dim = arch["kv_heads"] * arch["head_dim"]
    bias, layernorm = arch["bias"], arch["norm"] == "layernorm"
    shapes: Dict[str, Tuple[int, ...]] = {"embed": (V, H)}
    if arch["positions"] == "learned":
        shapes["pos"] = (arch["max_pos"], H)

    def norm(name):
        shapes[f"{name}.scale"] = (H,)
        if layernorm and bias:
            shapes[f"{name}.bias"] = (H,)

    def dense(name, d_in, d_out, with_bias):
        shapes[f"{name}.w"] = (d_in, d_out)
        if with_bias:
            shapes[f"{name}.b"] = (d_out,)

    for i in range(arch["layers"]):
        L = f"L{i}"
        norm(f"{L}.ln1")
        dense(f"{L}.q", H, q_dim, bias)
        dense(f"{L}.k", H, kv_dim, bias)
        dense(f"{L}.v", H, kv_dim, bias)
        dense(f"{L}.o", q_dim, H, bias)
        norm(f"{L}.ln2")
        if arch["mlp"] == "swiglu":
            dense(f"{L}.gate", H, F, False)
            dense(f"{L}.up", H, F, False)
            dense(f"{L}.down", F, H, False)
        else:
            dense(f"{L}.up", H, F, bias)
            dense(f"{L}.down", F, H, bias)
    norm("lnf")
    if not arch["tie"]:
        shapes["head"] = (H, V)
    return {prefix + k: v for k, v in shapes.items()}
