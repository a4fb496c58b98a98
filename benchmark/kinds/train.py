"""Traffic kind ``train``: one ``Launcher.launch()`` with the window inside.

The system under test is the trainer as a user builds it — ``Launcher ->
Looper -> Dataset -> Module -> Tracker`` — with two things of the
benchmark's own in the tree: a model adapter whose ``init_variables`` fills
the program's parameter tree from :mod:`benchmark.weights` (inside the
Module's one jitted init, so the weights are born on the device from the
seed), and a :class:`Window` capsule that runs after every step.

One compiled step with one state does everything: steps 1-3 are recorded for
the comparison with the plain reference, a few more warm the loop, then the
same object runs the measured window.  The window opens and closes on a
``block_until_ready`` of the state; in between the capsule only reads the
clock, so the loop's own dispatch-ahead is what is timed.
"""

from __future__ import annotations

import gc
import shutil
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import harness, traffic, weights

CHECK_STEPS = 3


def program(cell: harness.Cell, attention: Optional[str] = None):
    """The program's module for the cell's architecture at the training
    mix's sizes; ``attention`` overrides the mix's choice of kernel."""
    mix = cell.traffic
    return cell.family.program(
        cell.arch, max_seq=int(mix.get("max_seq", cell.arch["max_pos"])),
        attention=attention or mix.get("attention", "auto"))


def fill_tree(tree: Any, family: Any, arch: Dict, key: Any,
              prefix: str = "", dtype=None) -> Any:
    """The program's tree with every leaf replaced by the benchmark's leaf
    of that name, made on the device one group a call; a leaf the benchmark
    does not know, or of another shape, is an error.  ``family`` is the
    architecture's module (its ``leaf_shapes`` and ``leaf_name``); ``tree``
    may hold arrays or ``ShapeDtypeStruct``s."""
    import jax

    first = jax.tree_util.tree_leaves(tree)[0]
    leaves = weights.all_leaves(key, family.leaf_shapes(arch, prefix),
                                prefix, str(dtype or first.dtype))

    def one(path, old):
        name = prefix + family.leaf_name(path)
        if name not in leaves or tuple(old.shape) != leaves[name].shape:
            raise harness.BenchmarkError(
                f"program leaf {name} {tuple(old.shape)} is not the "
                f"benchmark's")
        return leaves[name]

    return jax.tree_util.tree_map_with_path(one, tree)


def named_leaves(tree: Any, family: Any) -> Dict[str, Any]:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {family.leaf_name(path): leaf for path, leaf in flat}


def _adam_mu(opt_state: Any):
    """The first-moment tree of the Adam state inside an optax chain."""
    stack = [opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, "mu") and hasattr(node, "nu"):
            return node.mu
        if isinstance(node, (tuple, list)):
            stack.extend(node)
    raise harness.BenchmarkError("no Adam state in the optimizer state")


def build(cell: harness.Cell, seed: int, window: "Window"):
    """The trainer, as ``examples/train_gpt2.py`` builds it."""
    import jax
    import jax.numpy as jnp
    import optax

    import rocket_tpu as rt
    from rocket_tpu.models.objectives import lm_cross_entropy
    from rocket_tpu.parallel.mesh import MeshSpec

    harness.log("program imported")
    mix, arch = cell.traffic, cell.arch
    opt = mix["optimizer"]

    data = {"tokens": traffic.markov_tokens(
        int(mix["docs"]), int(mix["seq"]), arch["vocab"], seed)}
    harness.log("data made")
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=opt["lr_init"], peak_value=opt["lr_peak"],
        warmup_steps=opt["warmup_steps"], decay_steps=opt["decay_steps"],
        end_value=opt["lr_end"])
    module = rt.Module(
        program(cell),
        capsules=[
            rt.Loss(lm_cross_entropy(), name="lm"),
            rt.Optimizer(tx_factory=optax.adamw, learning_rate=opt["lr_peak"],
                         grad_clip_norm=opt["clip_norm"], b1=opt["b1"],
                         b2=opt["b2"], eps=opt["eps"],
                         weight_decay=opt["weight_decay"]),
            rt.Scheduler(schedule),
        ],
        # eager: the state exists at set-up, where Window.setup() puts the
        # seed's weights into it before the first step
        input_spec={
            "tokens": jax.ShapeDtypeStruct(
                (int(mix["batch"]), int(mix["seq"])), jnp.int32),
            "_valid": jax.ShapeDtypeStruct((int(mix["batch"]),), jnp.bool_),
        })
    window.module = module
    root = harness.work_dir("train")
    shutil.rmtree(root, ignore_errors=True)
    launcher = rt.Launcher(
        capsules=[rt.Looper(capsules=[
            rt.Dataset(rt.ArraySource(data), batch_size=int(mix["batch"]),
                       shuffle=False, drop_last=True),
            module,
            rt.Tracker("jsonl"),
            window,
        ], progress=False)],
        tag="bench", num_epochs=1, mixed_precision=mix["mixed_precision"],
        # the chips the cell asks for and no others (data-parallel over them)
        mesh=MeshSpec().build(jax.devices()[:cell.chips]),
        # a constant: the program's own init is then one cached program
        # for every --seed; the seed's weights replace it in Window.setup()
        project_root=root, seed=0)
    return launcher, module


def _window_class():
    import rocket_tpu as rt

    class Window(rt.Capsule):
        """Runs after the Module and the Tracker in every iteration."""

        def __init__(self, cell, seed, seconds, compiles, trace,
                     warm_steps, trace_seconds):
            super().__init__(statefull=False, priority=50)
            self.cell, self.seed, self.seconds = cell, seed, seconds
            self.compiles, self.trace = compiles, trace
            self.warm_steps, self.trace_seconds = warm_steps, trace_seconds
            self.module = None
            self.i = 0
            self.batches: List[np.ndarray] = []
            self.losses: List[float] = []
            self.first_grad: Dict[str, float] = {}
            self.change: Dict[str, float] = {}
            self.t0 = self.t1 = None
            self.i0 = 0
            self.steps = 0

        def setup(self, attrs=None):
            """After the Module's set-up (it has the higher priority): the
            state exists; its parameters become the seed's, made on the
            device in one jitted call whose key is an argument."""
            import jax

            super().setup(attrs)
            harness.log("trainer set up; making the seed's weights")
            state = self.module.state
            made = fill_tree(state.params, self.cell.family,
                             self.cell.arch, weights.base_key(self.seed))
            # placed exactly as the program's own init places them, so the
            # step sees one signature (and compiles once), not one for the
            # first call and another for the outputs it then feeds back
            made = jax.tree_util.tree_map(
                lambda new, old: jax.device_put(new, old.sharding),
                made, state.params)
            self.module.state = state.replace(params=made)
            jax.block_until_ready(self.module.state.params)
            harness.log("weights on the device")

        def _block(self):
            import jax

            jax.block_until_ready(self.module.state.step)

        def launch(self, attrs=None):
            if attrs is None or attrs.step_logs is None:
                return
            self.i += 1
            i = self.i
            if i <= CHECK_STEPS:
                self._record(i, attrs)
                harness.log(f"step {i} recorded")
            if i < self.warm_steps:
                return
            if i == self.warm_steps:
                self._block()
                self.compiles.open()
                harness.log("window opens")
                self.t0, self.i0 = time.perf_counter(), i
                return
            now = time.perf_counter()
            if self.t1 is None:
                if now - self.t0 < self.seconds:
                    return
                self._block()
                self.t1 = time.perf_counter()
                self.compiles.close()
                self.steps = i - self.i0
                if self.trace is None:
                    attrs.looper.terminate = True
                    return
                # a traced run keeps stepping for a further stretch and
                # traces that: the profiler stalls nothing that is timed
                self.trace.start()
                self.trace_until = time.perf_counter() + self.trace_seconds
            elif now >= self.trace_until:
                self._block()
                self.trace.stop()
                attrs.looper.terminate = True

        def _record(self, i, attrs):
            import jax
            import jax.numpy as jnp

            self.batches.append(np.asarray(attrs.batch["tokens"]))
            self.losses.append(float(attrs.step_logs["loss"]))
            state = self.module.state
            opt = self.cell.traffic["optimizer"]
            if i == 1:
                norms = jax.jit(lambda t: jax.tree_util.tree_map(
                    lambda x: jnp.linalg.norm(x.astype(jnp.float32).ravel()),
                    t))(_adam_mu(state.opt_state))
                self.first_grad = {
                    k: float(v) / (1.0 - opt["b1"])
                    for k, v in named_leaves(
                        norms, self.cell.family).items()}
            if i == CHECK_STEPS:
                start = fill_tree(state.params, self.cell.family,
                                  self.cell.arch,
                                  weights.base_key(self.seed))
                change = jax.jit(lambda now, then: jax.tree_util.tree_map(
                    lambda a, b: jnp.linalg.norm(
                        (a.astype(jnp.float32) - b).ravel()), now, then))
                self.change = {k: float(v) for k, v in named_leaves(
                    change(state.params, start), self.cell.family).items()}
                del start

    return Window


def drive(cell: harness.Cell, *, seed: int, seconds: float, compiles,
          trace, process_start: float) -> Dict:
    mix = cell.traffic
    harness.arm_compile_cache()
    window = _window_class()(
        cell, seed, seconds, compiles, trace,
        warm_steps=int(mix.get("warm_steps", 6)),
        trace_seconds=float(mix.get("trace_seconds", 5.0)))
    launcher, module = build(cell, seed, window)
    harness.log("trainer built")
    launcher.launch()
    if window.t1 is None:
        raise harness.BenchmarkError(
            f"the data ran out after {window.i} steps, before the window "
            f"closed; raise 'docs' in the traffic file")
    peak = harness.memory_peak_bytes(cell.chips)
    # free the program's state before the reference takes the chip
    module.state = None
    del launcher, module
    window.module = None
    gc.collect()
    shutil.rmtree(harness.work_dir("train"), ignore_errors=True)
    tokens = window.steps * int(mix["batch"]) * int(mix["seq"])
    window_s = window.t1 - window.t0
    return {
        "attempted": window.steps, "failed": 0,
        "memory_peak_bytes": peak,
        "window_s": window_s, "steps": window.steps, "tokens": tokens,
        "setup_s": window.t0 - process_start,
        "end_to_end": {"train_tokens_per_s": tokens / window_s,
                       "setup_s": window.t0 - process_start},
        "recorded": {"batches": window.batches, "losses": window.losses,
                     "first_grad": window.first_grad,
                     "change": window.change},
        "host_spans": [],
    }


def reference_params(cell: harness.Cell, seed: int) -> Dict:
    return weights.all_leaves(weights.base_key(seed),
                              cell.family.leaf_shapes(cell.arch))


def gaps(recorded: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers compared: the worst step's relative loss gap, and by the
    worst leaf the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger.  Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone under Adam and
    are left out of the change."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(recorded["losses"], ref["losses"]))

    def worst(prog, want, keep):
        med = float(np.median(list(want.values())))
        return max((abs(prog[k] - want[k]) / max(want[k], med)
                    for k in want if keep(k)), default=0.0)

    g_med = float(np.median(list(ref["first_grad"].values())))
    moved = lambda k: ref["first_grad"][k] >= 1e-3 * g_med  # noqa: E731
    return {
        "loss_gap": float(loss_gap),
        "grad_gap": float(worst(recorded["first_grad"], ref["first_grad"],
                                lambda k: True)),
        "change_gap": float(worst(recorded["change"], ref["change"], moved)),
    }


def check(cell: harness.Cell, *, seed: int, run: Dict) -> Dict:
    rec = run["recorded"]
    params = reference_params(cell, seed)
    ref = cell.family.reference.train_steps(
        cell.arch, cell.traffic["optimizer"], params,
        rec["batches"][:CHECK_STEPS])
    del params
    gc.collect()
    return harness.judge(cell, gaps(rec, ref))
