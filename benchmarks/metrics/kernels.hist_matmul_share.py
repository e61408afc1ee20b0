"""Share of the device's busy time, in the traced dispatch, spent in matrix
multiplications and custom calls: the histogram kernel."""


def read(run: dict):
    t = run.get("trace")
    if not t:
        return None
    total = sum(t["class_s"].values())
    kernel = t["class_s"]["matmul"] + t["class_s"]["custom"]
    return 100.0 * kernel / total if total and kernel else None
