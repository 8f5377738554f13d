"""Share of the traced session's device idle time that no program span
explains, in %: each idle gap of the device (as `device_idle_share`
finds them) goes to the innermost `edgeol/` host span of the program
(`repro.obs.host`, read from the run's `.xplane.pb`) open at the gap's
midpoint, or to none. Notes the ten largest idle totals by program span.
Moves `images_per_s`."""

import programspans
import tracereduce

DEVICE_OPS = ("*",)


def read(ctx):
    try:
        path = tracereduce.find_xplane(programspans.trace_dir(ctx))
    except FileNotFoundError:
        return None
    spans = programspans.trace_spans(path, ctx.lo, ctx.hi)
    if not spans:
        return None
    by, outside = programspans.idle_by_program_span(ctx.trace, spans,
                                                    ctx.lo, ctx.hi)
    idle = outside + sum(by.values())
    if not idle:
        return None
    top = sorted(by.items(), key=lambda kv: -kv[1])[:10]
    ctx.note("idle_outside_program_spans: idle s by innermost program "
             "span: " + ", ".join(f"{k} {v / 1e9:.4f}" for k, v in top)
             + f"; outside {outside / 1e9:.4f} of {idle / 1e9:.4f}; "
             f"{len(spans)} program spans")
    return 100.0 * outside / idle
