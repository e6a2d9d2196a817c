"""Memory of a compiled program, as the compiler counts it."""


def program_bytes(compiled):
    """Peak bytes of one compiled program: arguments + outputs +
    temporaries - what outputs alias of arguments.  (On this runtime
    ``memory_stats()["peak_bytes_in_use"]`` counts live buffers only and
    leaves a program's temporaries out; PERF.md, section 7.)"""
    m = compiled.memory_analysis()
    if m is None:
        return None
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)
