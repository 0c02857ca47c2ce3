"""Mean milliseconds of the engine's ``serve.decode_step`` span (host
dispatch through the blocking argmax harvest; ``ServeEngine.step_batch``)
inside the traced window."""
import readers


def read(ctx):
    return readers.span_mean_ms(ctx, "serve.decode_step")
