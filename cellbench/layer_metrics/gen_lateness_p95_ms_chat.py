"""How late the generator sent: 95th percentile of (actual submit - due)."""


def read(observed):
    return observed["counters"].get("lateness_p95_ms")
