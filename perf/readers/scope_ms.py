"""Device time of the ops under some scopes of the program, ms a step a
chip: each leaf op's own time (``program_trace.own_ns``) where its
``op_name`` has one of ``scopes`` among its path segments (a flax module's
name or a ``jax.named_scope``: ``conv``, ``moe_mlp``, ``route``), every one
of ``under`` too, and, if ``phases`` is given, one of those phases (``fwd``,
``bwd``, ``opt``, ``other``). ``kind`` keeps one kind of instruction only
(``tpu_custom_call``: the Pallas kernels). Nothing to read (no trace, a
program that hands out no op names, or no such op in the window): ``None``."""

from perf import program_trace


def scope_ns(obs, scopes, under=(), phases=None, kind=None):
    """Summed own device ns over the chips, or None without a named trace."""
    trace = program_trace.of(obs)
    if trace is None or not trace.op_names:
        return None
    total = 0
    for events in trace.devices.values():
        for ev, ns in program_trace.own_ns(events, obs.trace_window):
            path = trace.op_names.get(ev.name, "")
            segs = path.split("/")
            if (any(s in segs for s in scopes)
                    and all(s in segs for s in under)
                    and (kind is None or ev.detail == kind)
                    and (phases is None
                         or program_trace.phase_of(path) in phases)):
                total += ns
    return total


def read(obs, *, scopes, under=(), phases=None, kind=None):
    ns = scope_ns(obs, scopes, under, phases, kind)
    if not ns:
        return None
    steps, chips = obs.counters["steps"], len(program_trace.of(obs).devices)
    return ns / (1e6 * steps * chips)
