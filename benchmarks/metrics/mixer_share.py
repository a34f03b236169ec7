"""The mixers' share of the compiled step: (``flash_attn_ms`` +
``moe_route_ms`` + ``moe_experts_ms``) over ``step_device_ms``."""

import mixer_trace


def read(run):
    got, t = mixer_trace.get(run), run["trace"]
    if not got or not t or not t["step_device_s"]:
        return None
    return 100.0 * sum(got["ms"].values()) / (1e3 * t["step_device_s"])
