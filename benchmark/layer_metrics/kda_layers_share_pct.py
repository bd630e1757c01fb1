"""Train step: device-op time under ``attn/kda`` (the KDA mixers:
projections, convolutions, gates and the scan) over all device-op time
of the traced steps -- the share of the step the new mechanism takes."""


def read(facts):
    scopes = facts.get("hybrid_scopes")
    if not scopes or not scopes.get("device_op_s"):
        return None
    kda = sum(scopes["scope_s"].get(s, 0.0) for s in ("kda", "kda_scan"))
    return 100.0 * kda / scopes["device_op_s"]
