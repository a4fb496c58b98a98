"""Dispatcher — composite capsule that fans events out to children.

Capability parity: reference ``rocket/core/dispatcher.py:22-255``.  Semantics
preserved:

- children sorted by ``priority`` **descending** at construction
  (``dispatcher.py:54-56``);
- ``destroy`` traverses children in **reverse** order (``dispatcher.py:94``),
  which is what makes the checkpoint-registry LIFO invariant hold
  (see :class:`~rocket_tpu.core.capsule.Capsule`);
- runtime binding recurses into the whole subtree (``dispatcher.py:161-180``);
- ``guard`` validates child types (``dispatcher.py:198-223``).
"""

from __future__ import annotations

from typing import Any, Iterable, List, Optional

from rocket_tpu.core.attributes import Attributes
from rocket_tpu.core.capsule import Capsule

# Lazy handle to observe.trace.span — resolved on first dispatch, NOT at
# import (rocket_tpu.observe imports core capsules, so a top-level import
# here would be circular).
_SPAN = None


def _span(name: str, **fields: Any):
    global _SPAN
    if _SPAN is None:
        from rocket_tpu.observe.trace import span

        _SPAN = span
    return _SPAN(name, **fields)


class Dispatcher(Capsule):
    """Composite capsule: holds an ordered list of children and dispatches
    every lifecycle event to them."""

    def __init__(
        self,
        capsules: Iterable[Capsule] = (),
        statefull: bool = False,
        priority: int = 1000,
        logger: Optional[Any] = None,
    ) -> None:
        super().__init__(statefull=statefull, priority=priority, logger=logger)
        self._capsules: List[Capsule] = list(capsules)
        self.guard()
        self._capsules.sort(key=lambda c: c.priority, reverse=True)

    # -- lifecycle fan-out --------------------------------------------------

    def _event(self, capsule: Capsule, event: str,
               attrs: Optional[Attributes]) -> None:
        """Dispatch one lifecycle event to one child under its
        ``<Capsule>.<event>`` span: a profiler annotation always, a ring
        event when tracing is armed — one path either way."""
        with _span(f"{type(capsule).__name__}.{event}", cat="capsule"):
            getattr(capsule, event)(attrs)

    def _launch_children(self, attrs: Optional[Attributes]) -> None:
        """The hot loops' fan-out (``Looper`` each iteration, ``Module``
        to its Loss/Optimizer/Scheduler): every child's ``launch`` under
        its own ``<Capsule>.launch`` span."""
        for capsule in self._capsules:
            self._event(capsule, "launch", attrs)

    def setup(self, attrs: Optional[Attributes] = None) -> None:
        super().setup(attrs)
        for capsule in self._capsules:
            self._event(capsule, "setup", attrs)

    def destroy(self, attrs: Optional[Attributes] = None) -> None:
        for capsule in reversed(self._capsules):
            self._event(capsule, "destroy", attrs)
        super().destroy(attrs)

    def set(self, attrs: Optional[Attributes] = None) -> None:
        super().set(attrs)
        for capsule in self._capsules:
            self._event(capsule, "set", attrs)

    def reset(self, attrs: Optional[Attributes] = None) -> None:
        super().reset(attrs)
        for capsule in self._capsules:
            self._event(capsule, "reset", attrs)

    def launch(self, attrs: Optional[Attributes] = None) -> None:
        super().launch(attrs)
        self._launch_children(attrs)

    # -- runtime ------------------------------------------------------------

    def bind(self, runtime: Any) -> None:
        super().bind(runtime)
        for capsule in self._capsules:
            capsule.bind(runtime)

    def clear(self) -> None:
        super().clear()
        for capsule in self._capsules:
            capsule.clear()

    # -- validation / introspection -----------------------------------------

    def guard(self) -> None:
        for capsule in self._capsules:
            if not isinstance(capsule, Capsule):
                raise TypeError(
                    f"{type(self).__name__} children must be Capsules, got "
                    f"{type(capsule).__name__}"
                )

    @property
    def capsules(self) -> List[Capsule]:
        return list(self._capsules)

    def __repr__(self) -> str:
        head = super().__repr__()
        if not self._capsules:
            return head
        lines = [head[:-1] if head.endswith(")") else head]
        body = []
        for capsule in self._capsules:
            child = repr(capsule)
            child = "\n".join("    " + ln for ln in child.splitlines())
            body.append(child)
        return lines[0] + ",\n  capsules=[\n" + ",\n".join(body) + "\n  ])"
