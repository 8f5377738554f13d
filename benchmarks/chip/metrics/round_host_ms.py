"""Host time of a fine-tuning round, in ms per round, from the program's
own spans (`RunResult.host`) over every session of the untraced window of
the traced run: the self time of every span at or under `round`
(`DeviceRuntime.finish_round`: launch and completion; its children
`round/own_buffers`, `round/cost`, `round/publish`, `round/validate`,
`round/policy` and the shared `train/stage`, `train/dispatch`), leaving
out serving and the CKA probe when a round runs them (`serve/*`, `cka/*`
and what lies under them, which their own metrics read), over the
`round` spans. Moves `images_per_s`."""

import programspans

DEVICE_OPS = ()


def read(ctx):
    hs = programspans.hosts(ctx.window_logs)
    if hs is None:
        return None
    by, rounds = {}, 0
    for h in hs:
        for path, s in h["spans"].items():
            parts = programspans.names(path)
            if "round" not in parts:
                continue
            if parts[-1] == "round":
                rounds += s["count"]
            under = parts[parts.index("round"):]
            if not any(p.startswith(("serve/", "cka/")) for p in under):
                by[parts[-1]] = by.get(parts[-1], 0.0) + s["self_s"]
    if not rounds:
        return None
    ctx.note("round_host_ms: self ms per round by span: " + ", ".join(
        f"{k} {1e3 * v / rounds:.3f}"
        for k, v in sorted(by.items(), key=lambda kv: -kv[1])))
    return 1e3 * sum(by.values()) / rounds
