"""Train step: device-op time under ``mtp`` (the prediction module: its
join, its block, its head and second loss, forward and backward) over
all device-op time of the traced steps -- the share of the step the
module takes."""


def read(facts):
    scopes = facts.get("mtp_scopes")
    if not scopes or not scopes.get("device_op_s"):
        return None
    if not scopes.get("module_s"):
        return None
    return 100.0 * scopes["module_s"] / scopes["device_op_s"]
