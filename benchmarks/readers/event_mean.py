"""Mean of one field over the window's telemetry events of one kind;
with ``over_config`` divided by that configuration value, with
``percent`` times 100."""


def read(spec, run):
    vals = [float(e[spec["field"]]) for e in run.events
            if e.get("kind") == spec["event"] and spec["field"] in e]
    if not vals:
        return None
    mean = sum(vals) / len(vals)
    if "over_config" in spec:
        mean /= float(run.config[spec["over_config"]])
    return mean * (100.0 if spec.get("percent") else 1.0)
