"""The program's ``LLMDeployment`` plus a device-trace hook, and nothing
else.  Only the process that holds the chip can trace it, and the replica
has no such method yet (PERF.md, Open questions: the ``tracing`` issue
should move this hook into ``ray_tpu.serve.llm``)."""

from ray_tpu.serve.llm import LLMDeployment


class TracedLLMDeployment(LLMDeployment):
    def start_trace(self, log_dir: str) -> str:
        import jax

        # device and XLA host events only: the Python tracer slows the
        # engine's host loop, which is what the idle share measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        return log_dir

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()
