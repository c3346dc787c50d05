"""Device memory the fullest chip holds at the window's end
(`memory_stats()["bytes_in_use"]`: the weights and the KV pool), in GB
(1e9 bytes). The process-lifetime peak is `init_inference`'s transient
and is in the result line's `device`."""


def read(obs):
    b = obs.get("hbm_in_use_bytes")
    return None if not b else b / 1e9
