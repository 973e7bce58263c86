"""Names the program gives its work in a profiler trace.

Device scopes are ``jax.named_scope``s inside the jitted train step:
compile-time metadata only.  Each op of the compiled step carries its
scope as a component of its HLO ``op_name``
(``jit(train_step)/.../pipe.B/transpose(jvp(pipe.head))/dot_general``);
the innermost ``pipe.*``/``opt.*`` component names the op's work.

Host spans are ``jax.profiler.TraceAnnotation``s: TraceMes on the
profiler's clock, the clock of the device events, so a gap in a chip's
work can be put down to what the host was doing then.  Outside an active
trace a span costs about a microsecond.

``python -m repro.launch.train --trace-dir DIR`` writes a trace that
holds both.
"""
from __future__ import annotations

import jax

# device scopes of make_train_step
PIPE_F = "pipe.F"            # forward tick: one stage on one micro-batch
PIPE_B = "pipe.B"            # backward tick: recompute + vjp of the stage
PIPE_W = "pipe.W"            # zero-bubble weight-gradient tick
PIPE_HEAD = "pipe.head"      # final norm, tied logits, cross-entropy
PIPE_SEND = "pipe.send"      # the forward and backward ring ppermutes
PIPE_AR = "pipe.ar"          # in-scan data-parallel gradient bucket sync
PIPE_EMBED = "pipe.embed"    # token embedding and its backward
PIPE_SYNC = "pipe.sync"      # loss and gradient psums after the scan
OPT_UPDATE = "opt.update"    # the optimizer's update of params and state

# host spans of the data layer (repro.data.synthetic)
DATA_BUILD = "data.build"    # the numpy bigram chain of one batch
DATA_PUT = "data.put"        # host-to-device copies of a batch


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span: ``with span(DATA_BUILD): ...``."""
    return jax.profiler.TraceAnnotation(name)
