"""Program spans in the JAX profiler's trace.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation`` named
``dsd.<name>``: a host span written into the profiler's own trace, on the
clock of the device events beside it. With no profiler active it costs
one ``TraceMe`` check. Metadata are host values the caller already holds
(ints, floats, strings); a span never waits on the device and never sits
inside a jitted function.

Capture a serve with the profiler around it::

    with jax.profiler.trace("/tmp/dsd-trace"):
        serve(...)

Nesting, outermost first: ``dsd.serve`` ⊃ (``dsd.serve.idle`` |
``dsd.serve.route`` | ``dsd.admit`` | ``dsd.chunk`` | ``dsd.retire``), the
admissions following the route that chose them; ``dsd.admit`` ⊃
``dsd.admit.dispatch``, ``dsd.admit.wait``; ``dsd.chunk`` ⊃
``dsd.decide``, ``dsd.round``, ``dsd.chunk.sync``, ``dsd.chunk.commit``;
``dsd.round`` ⊃ ``dsd.step.dispatch`` (colocated) or the transport round's
``dsd.propose``, ``dsd.verify``, ``dsd.verdict.sync``, ``dsd.ingest``,
``dsd.advance`` and ``dsd.link.*`` (every wait on the link).
"""

from __future__ import annotations

import jax

PREFIX = "dsd."

SPANS = (
    "dsd.serve",            # SpecDecodeServer.run, from the arrival clock
    "dsd.serve.idle",       # waiting for the next arrival
    "dsd.serve.route",      # routing, SLO gate, admission choice
    "dsd.retire",           # one retirement (request_id)
    "dsd.admit",            # prefill-insert (request_id, arrival_s, prompt_len)
    "dsd.admit.dispatch",   # argument build + insert dispatch
    "dsd.admit.wait",       # wait for the first token
    "dsd.chunk",            # one decode chunk (active slots)
    "dsd.round",            # one speculation round (gamma, fused)
    "dsd.decide",           # window-policy decision
    "dsd.step.dispatch",    # colocated step dispatch
    "dsd.chunk.sync",       # chunk-end host transfers
    "dsd.chunk.commit",     # marker: tokens, slot_rounds, fused of the chunk
    "dsd.propose",          # draft propose dispatch + window to the host
    "dsd.verify",           # target verify/commit dispatch
    "dsd.verdict.sync",     # host waits on a verify's outputs
    "dsd.ingest",           # draft ingest dispatch
    "dsd.advance",          # recurrent draft re-advance dispatch
    "dsd.link.window",      # window delivery wait
    "dsd.link.verdict",     # verdict delivery wait
    "dsd.link.control",     # control round trip (fused token-stream flush)
)


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """The profiler span ``dsd.<name>`` (listed in ``SPANS``), carrying
    ``meta`` as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)
