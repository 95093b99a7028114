"""Host spans of the serving engine: what the scheduler was doing, when.

One recorder per engine.  ``Spans.span(name, **attrs)`` is a context
manager that records ``Span(id, name, start_ns, end_ns, parent, attrs)``
on ``time.perf_counter_ns()``; ``parent`` is the id of the span open
around it (``None`` at the top).  The same call enters a
``jax.profiler.TraceAnnotation`` of the same name and attributes, so a
profiled run shows the engine's phases on the device trace's own clock;
attributes known only at the end (``Open.set``) reach the annotation too.
``Spans.mark(name, start_ns, end_ns, rid=...)`` records an interval that
does not nest: a request's stay in the queue, in prefill, in decode.

Spans are kept in memory in a ring of ``CAPACITY`` records, oldest
dropped first; ``dropped`` counts what fell out.  The recorder is always
on: with the profiler off a span costs two clock reads, one annotation
that records nothing, and one tuple.  Nothing here formats strings.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

#: records held: at about a dozen spans a ``step()`` and a few dozen
#: steps a second, several minutes of serving
CAPACITY = 1 << 17


class Span(NamedTuple):
    id: int
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]
    attrs: Dict[str, int]


class Open:
    """A span being recorded (what ``Spans.span`` returns)."""
    __slots__ = ("_rec", "_ann", "id", "name", "attrs", "parent",
                 "start_ns", "end_ns")

    def __init__(self, rec: "Spans", name: str, attrs: dict):
        self._rec, self.name, self.attrs = rec, name, attrs
        self._ann = TraceAnnotation(name, **attrs)

    def __enter__(self) -> "Open":
        rec = self._rec
        stack = rec._stack
        self.id = rec._next
        rec._next += 1
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        rec = self._rec
        rec._stack.pop()
        rec._ring.append((self.id, self.name, self.start_ns, self.end_ns,
                          self.parent, self.attrs))

    def set(self, **attrs) -> None:
        """Attributes known only inside the span (a count it produced)."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    @property
    def seconds(self) -> float:
        """Duration of the closed span."""
        return (self.end_ns - self.start_ns) * 1e-9


class Spans:
    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self.reset()

    def reset(self) -> None:
        self._ring: collections.deque = collections.deque(
            maxlen=self.capacity)
        self._stack: List[int] = []
        self._next = 0

    def span(self, name: str, **attrs) -> Open:
        return Open(self, name, attrs)

    def mark(self, name: str, start_ns: int, end_ns: int, **attrs) -> None:
        self._ring.append((self._next, name, start_ns, end_ns, None, attrs))
        self._next += 1

    @property
    def dropped(self) -> int:
        """Records that fell out of the ring since ``reset``."""
        return self._next - len(self._stack) - len(self._ring)

    def records(self) -> List[Span]:
        """Every record held, in the order each closed."""
        return [Span(*r) for r in self._ring]
