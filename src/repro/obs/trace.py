"""Host spans on the profiler's clock, and the CLIs' profiler window.

``span(name, **args)`` marks host work: the train CLI's ``train_step``,
``replan`` and ``migrate``, ``ckpt_save``/``ckpt_restore``, the serve CLI's
``decode_step``.  Off by default, it is then a no-op costing one global
read, so the instrumented paths keep their spans unconditionally.  Once
:func:`configure` turns it on, a span enters a
``jax.profiler.TraceAnnotation`` of the same name: a profiler trace then
holds it on the host plane, on one clock with the device ops and the
program's named scopes (``repro.obs.scopes``).

:class:`Window` records such a trace over a few steps after warm-up
(``--trace DIR`` of ``launch/train.py`` and ``launch/serve.py``): the
``.xplane.pb`` under ``DIR/plugins/profile/<run>/`` and a Perfetto
``perfetto_trace.json.gz`` beside it (https://ui.perfetto.dev opens it).

JAX is imported only when a span or window is used, so ``repro.core`` can
import this package without cycles.
"""
from __future__ import annotations

from contextlib import contextmanager

WINDOW_STEPS = 5  # steps a --trace window records

_ENABLED = False


def configure(*, enabled: bool = True) -> None:
    """Turn the spans on (or off) for this process."""
    global _ENABLED
    _ENABLED = enabled


def enabled() -> bool:
    return _ENABLED


@contextmanager
def span(name: str, **args):
    """A ``TraceAnnotation`` named ``name`` carrying ``args`` while spans are
    on; nothing otherwise."""
    if not _ENABLED:
        yield
        return
    from jax.profiler import TraceAnnotation

    with TraceAnnotation(name, **args):
        yield


class Window:
    """A profiler trace of steps ``[first, first + steps)`` into ``log_dir``.

    Call :meth:`at` with each step's index before running it, and
    :meth:`close` once the loop ends (it stops a window the run cut short).
    """

    def __init__(self, log_dir: str, first: int, steps: int = WINDOW_STEPS):
        self.log_dir, self.first, self.last = log_dir, first, first + steps
        self.on = False

    def at(self, step: int) -> None:
        import jax

        if step == self.first and not self.on:
            jax.profiler.start_trace(self.log_dir, create_perfetto_trace=True)
            self.on = True
        elif step == self.last:
            self.close()

    def close(self) -> None:
        import jax

        if self.on:
            jax.profiler.stop_trace()
            self.on = False
