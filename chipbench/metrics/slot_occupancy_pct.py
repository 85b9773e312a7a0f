"""Mean share of the slots that decoded in a tick (the rest were empty
or still admitting their prompt). Layer: server."""


def read(run):
    ticks = run.info.get("ticks")
    if not ticks:
        return None
    return 100.0 * sum(d for _, _, d in ticks) / (
        len(ticks) * run.info["slots"]
    )
