"""Bytes on the wire of the aura exchange per step, summed over chips:
the program's own counter ``SimState.halo_bytes`` (bytes of the last aura
update) read after each call of the traced window, mean over calls."""


def read(ctx):
    hb = ctx["halo_bytes_per_step"]
    if not hb or not any(hb):
        return None
    return sum(hb) / len(hb)
