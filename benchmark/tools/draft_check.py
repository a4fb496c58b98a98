"""A hidden-state draft's proposals against the plain reference, on the chip
at the cell's sizes, once.

    python benchmark/tools/draft_check.py --workload <name> --seed 7 --prompt 512

A cell's ``served_gap`` cannot see the draft: greedy acceptance emits the
target's own arg-maxes whatever the draft proposes.  This runs the program's
target over one prompt, then its draft over the target's hidden states (the
admission's two passes), and compares the draft's logits at every position
with ``reference.mtp_logits`` in float32 on the same served weights: the
widest gap by which the proposed token's reference logit lies below the
reference's best, in units of that position's logit spread (what
``served_gap`` is for the target), and how often both propose the same
token.  One JSON line; PERF.md keeps the reading.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--prompt", type=int, default=512)
    parser.add_argument("--device", choices=("tpu", "any"), default="tpu")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import harness, weights
    from benchmark.kinds import serving

    cell = harness.resolve_cell(args.workload)
    if args.device == "tpu":
        harness.require_chips(cell)
    family, arch = cell.family, cell.arch
    draft_arch = family.draft(arch, cell.config["serving"])
    model, draft, abs_t, abs_d = serving.program_models(cell)
    if not getattr(draft, "reads_hidden", False):
        raise SystemExit("the cell's draft reads no hidden state")
    params, draft_params = serving.make_params(cell, args.seed, abs_t, abs_d)
    rng = np.random.default_rng(args.seed)
    prompt = jnp.asarray(rng.integers(0, arch["vocab"], size=(1, args.prompt)),
                         jnp.int32)
    pos = jnp.arange(args.prompt, dtype=jnp.int32)[None]

    @jax.jit
    def program(params, draft_params):
        # a decode pass hands out the hidden states; with no cache given it
        # attends within the prompt, as an admission's prefill does
        out = model.apply({"params": params},
                          {"tokens": prompt, "positions": pos},
                          decode=True, mutable=["cache"])[0]
        g = jnp.argmax(out["logits"][:, -1].astype(jnp.float32), -1)
        nxt = jnp.concatenate([prompt[:, 1:], g[:, None].astype(jnp.int32)], 1)
        d_out = draft.apply(
            {"params": draft_params},
            {"tokens": nxt, "positions": pos, "hidden": out["hidden"],
             **draft.tied(params)})
        return nxt, d_out["logits"][0].astype(jnp.float32)

    nxt, got = program(params, draft_params)
    got = np.asarray(got)
    tokens = np.concatenate([np.asarray(prompt[0, :1]), np.asarray(nxt[0])])
    del params, draft_params
    weights.release()

    dtype = str(jnp.dtype(cell.config["serving"]["weights_dtype"]))
    key = weights.base_key(args.seed)

    def getter(a, prefix=""):
        shapes = weights.groups(family.leaf_shapes(a, prefix), prefix)
        return lambda group: {
            k: v.astype(jnp.float32) for k, v in weights.make_group(
                key, group, shapes[group], dtype).items()}

    ref = np.asarray(family.reference.mtp_logits(
        arch, draft_arch, "f32", getter(arch), getter(draft_arch, "draft."),
        tokens))
    proposed = got.argmax(-1)
    gap = (ref.max(-1) - np.take_along_axis(ref, proposed[:, None], -1)[:, 0]
           ) / ref.std(-1)
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "positions": len(gap),
        "draft_gap": float(gap.max()),
        "same_proposal_share": float((proposed == ref.argmax(-1)).mean()),
        "logit_rms_error_over_spread": float(
            np.sqrt(((got - ref) ** 2).mean()) / ref.std())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
