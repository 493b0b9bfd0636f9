"""Bus bytes of the traced calls over the chips' collective time, as a
share of the published ICI bandwidth per chip: what the collectives
achieve while they run (bench/trace.py counts an asynchronous collective
from its start to its done)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr.get("collective_s") or not rec.get("trace_bus_bytes"):
        return None
    return (100.0 * rec["trace_bus_bytes"] / tr["collective_s"]
            / rec["peaks"]["ici_bytes_per_s"])
