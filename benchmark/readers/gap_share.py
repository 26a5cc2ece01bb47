"""The program's device-gap ledger over the window, in per cent: the seconds
of `es_device_gap_seconds_total{during=...}` (all labels, or all but
`except`) over the window's length (`over: window`) or over the seconds of all
labels (`over: gap`). A gap is a stretch in which the host saw no program in
flight; `during` says what the thread that ended it was doing."""


def read(ctx, params):
    name = "es_device_gap_seconds_total"
    after = ctx["after"]["metrics"].get(name)
    if not after:
        return None
    before = {lab.get("during"): v
              for lab, v in ctx["before"]["metrics"].get(name, [])}
    gaps = {lab.get("during"): v - before.get(lab.get("during"), 0.0)
            for lab, v in after}
    base = ctx["window_s"] if params["over"] == "window" \
        else sum(gaps.values())
    if not base:
        return None
    left_out = set(params.get("except", []))
    return 100.0 * sum(v for during, v in gaps.items()
                       if during not in left_out) / base
