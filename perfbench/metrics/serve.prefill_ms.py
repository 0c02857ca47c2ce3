"""Device milliseconds per chunked-prefill dispatch (the engine's
jitted prefill step), from the trace; the host span of the program spans
only the enqueue and is not used."""
import readers


def read(ctx):
    return readers.module_ms_per_call(ctx, ("jit__prefill_impl",))
