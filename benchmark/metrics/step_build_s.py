"""Model step: seconds the start-up spent tracing, lowering and compiling
(or loading from the compilation cache) the step's modules, summed over
the engine's step records that ended before the window opened."""
from harness.program_gaps import step_build_s as read  # noqa: F401
