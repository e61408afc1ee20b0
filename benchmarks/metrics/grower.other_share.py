"""Share of the device's busy time, in the traced dispatch, spent in
operations that are neither matrix multiplications nor custom calls: the
grower's routing, partition, compaction, split scan and loop bodies."""


def read(run: dict):
    t = run.get("trace")
    if not t or not sum(t["class_s"].values()):
        return None
    return 100.0 * t["class_s"]["other"] / sum(t["class_s"].values())
