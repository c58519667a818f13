"""The decode program's temporaries as the compiler reports them
(`memory_analysis().temp_size_in_bytes` through the program registry),
in GB (1e9 bytes): what `peak_hbm_gb.serve` does not see."""


def read(ctx):
    try:
        from deepspeed_tpu.monitor import programs
    except ImportError:              # the program has no registry yet
        return None
    memory = programs.memory("jit_decode_fn")
    return None if memory is None else memory["temp"] / 1e9
