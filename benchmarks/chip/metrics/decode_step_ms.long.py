"""Device time of the fused decode program per decode step it ran
(trace and the engine's step counter)."""
import derive


def read(run):
    return derive.step_ms(run, derive.DECODE_PROGRAM, "decode_steps")
