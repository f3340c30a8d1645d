"""Continuous-batching text-generation serving (ROADMAP north-star pillar 3).

`ServingEngine` (engine.py) is the core: a batched ring KV cache of static
[max_batch_slots, cache_capacity] shape, ONE compiled decode step advancing every
active slot per dispatch, and a plain-Python scheduler that admits queued requests
into freed slots at token boundaries. `serve.py` is the DI/CLI glue
(`inference_component.serve`): it replays a requests file or serves HTTP; the
repo ships no load generator."""

from modalities_tpu.serving.engine import ServeRequest, ServeResult, ServingEngine

__all__ = ["ServeRequest", "ServeResult", "ServingEngine"]
