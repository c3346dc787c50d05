"""Device memory the fullest chip holds at the window's end
(`memory_stats()["bytes_in_use"]`: fp32 master, optimizer state and the
bf16 copy; activations live only inside a step), in GB (1e9 bytes). The
process-lifetime peak is reached in the engine's init and is in the
result line's `device`."""


def read(obs):
    b = obs.get("hbm_in_use_bytes")
    return None if not b else b / 1e9
