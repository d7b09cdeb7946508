"""TPUService: the serving backend — wraps InferenceEngine behind the
BaseService contract (the role HFService plays in the reference,
services.py:27-116, with torch generate swapped for the jit engine).
"""

from __future__ import annotations

import time
from typing import Any, Iterator

from ..tracing import current_timing
from .base import (
    BaseService,
    ServiceError,
    normalize_stops,
    parse_transcript,
    pump_turn,
    role_cut,
    scrub_stop_words,
    scrub_stream_delta,
    stop_cut,
)


class TPUService(BaseService):
    def __init__(
        self,
        model_name: str,
        price_per_token: float = 0.0,
        max_new_tokens: int = 2048,
        engine=None,
        mesh=None,
        checkpoint_path: str | None = None,
        engine_config=None,
        lora_path: str | None = None,
    ):
        super().__init__("tpu")
        self.model_name = model_name
        self.price_per_token = price_per_token
        self.max_new_tokens = max_new_tokens
        self.engine = engine
        self._mesh = mesh
        self._checkpoint_path = checkpoint_path
        self._engine_config = engine_config
        self._lora_path = lora_path

    @property
    def stream_rows(self) -> int:
        cfg = (self.engine.engine_cfg if self.engine is not None
               else self._engine_config)
        return int(cfg.max_batch) if cfg is not None else 0

    # loading is split from construction so nodes can announce before the
    # (slow) compile finishes — same shape as the reference's load_sync/
    # load_async split (services.py:36-41)
    def load_sync(self):
        if self.engine is None:
            from ..engine.engine import InferenceEngine

            self.engine = InferenceEngine(
                self.model_name,
                mesh=self._mesh,
                checkpoint_path=self._checkpoint_path,
                engine_config=self._engine_config,
                lora_path=self._lora_path,
            )
            # every prefill program an admission burst can ask for, before
            # the node announces the model (scheduler.warm_prefill)
            self.engine.scheduler.warm_prefill()
        if self.model_name in (None, "", "auto"):
            # `--model auto`: advertise the name the checkpoint's config
            # resolved to, not the sentinel
            self.model_name = self.engine.model_cfg.name
        return self

    def get_metadata(self) -> dict[str, Any]:
        meta = {
            "models": [self.model_name],
            "price_per_token": self.price_per_token,
            "max_new_tokens": self.max_new_tokens,
            "backend": "tpu",
        }
        if self.engine is not None:
            meta["engine"] = self.engine.info
            meta["measured"] = self.engine.metrics.snapshot()
            resident = self.engine.resident_adapters()
            if resident:
                # per-adapter model names (adapters/): "<base>:<name>"
                # rides hello/announce metadata so the mesh can route an
                # adapter request straight to a node already holding it
                from ..adapters import adapter_model_name

                meta["adapters"] = resident
                meta["models"] = [self.model_name] + [
                    adapter_model_name(self.model_name, a) for a in resident
                ]
        return meta

    def _gen_args(self, params: dict) -> dict:
        prompt = self._require_prompt(params)
        messages, was_transcript = parse_transcript(prompt)
        if was_transcript:
            # flatten back to a plain prompt ending with the assistant cue;
            # a real chat template would need a real tokenizer, which a
            # zero-egress node may not have
            prompt = "\n".join(f"{m['role']}: {m['content']}" for m in messages)
            prompt += "\nassistant:"
        return {
            "prompt": prompt,
            "max_new_tokens": min(
                int(params.get("max_new_tokens", self.max_new_tokens)), self.max_new_tokens
            ),
            "temperature": float(params.get("temperature", 0.7)),
            "top_k": int(params.get("top_k", 0)),
            "top_p": float(params.get("top_p", 1.0)),
            "min_p": float(params.get("min_p", 0.0)),
            "repetition_penalty": float(params.get("repetition_penalty", 1.0)),
            "presence_penalty": float(params.get("presence_penalty", 0.0)),
            "frequency_penalty": float(params.get("frequency_penalty", 0.0)),
            # fairness identity (router/): keys the scheduler's WDRR queue
            "tenant": str(params.get("tenant") or "default"),
            # multi-adapter serving (adapters/): which pool adapter this
            # generation decodes under (None = base model). The engine
            # raises a typed UnknownAdapter for anything non-resident.
            "adapter": params.get("adapter") or None,
        }

    def execute(self, params: dict[str, Any]) -> dict[str, Any]:
        if self.engine is None:
            raise ServiceError("Model not loaded")
        t0 = time.time()
        stops = normalize_stops(params.get("stop"))
        if stops:
            # route through the streaming path: the engine early-exits at
            # the stop hit (generate_stream's close releases the row), so
            # a 2048-budget request stopping at token 10 neither computes
            # nor BILLS the ~2038 discarded tokens (OpenAI semantics)
            return self._execute_with_stops(params, stops, t0)
        args = self._gen_args(params)
        result = self.engine.generate(**args)
        text = scrub_stop_words(result.text)
        out = self.result_dict(text, result.new_tokens, t0, self.price_per_token)
        out["tokens_per_sec"] = result.tokens_per_sec
        out["ttft_ms"] = int(result.ttft_s * 1000)
        out["finish_reason"] = result.finish_reason
        out["prompt_tokens"] = result.prompt_tokens  # /v1 usage accounting
        # the per-request latency breakdown (queue_wait/prefill/ttft/
        # tokens_per_s/spec_acceptance): rides gen_success frames so the
        # requester sees where its latency went (ISSUE 5)
        out["timing"] = dict(result.timings)
        return out

    def _execute_with_stops(self, params: dict, stops: tuple, t0: float) -> dict:
        args = self._gen_args(params)
        acc, n_seen, hit, result = "", 0, False, None
        gen = self.engine.generate_stream(**args)
        try:
            for ev in gen:
                if ev.get("done"):
                    result = ev.get("result")
                    break
                acc += ev.get("text", "")
                n_seen += len(ev.get("tokens") or ([1] if ev.get("token") is not None else []))
                if stop_cut(acc, stops) is not None:
                    hit = True  # closing the generator cancels the row
                    break
        finally:
            gen.close()
        rc, sc = role_cut(acc), stop_cut(acc, stops)
        text = acc[:rc if sc is None else min(rc, sc)]
        n_tokens = result.new_tokens if result is not None else n_seen
        out = self.result_dict(text, n_tokens, t0, self.price_per_token)
        out["finish_reason"] = (
            "stop" if hit or (sc is not None and sc <= rc)
            else (result.finish_reason if result else "stop")
        )
        if result is not None:
            out["tokens_per_sec"] = result.tokens_per_sec
            out["ttft_ms"] = int(result.ttft_s * 1000)
            out["prompt_tokens"] = result.prompt_tokens
            out["timing"] = dict(result.timings)
        return out

    def execute_stream(self, params: dict[str, Any]) -> Iterator[str]:
        if self.engine is None:
            raise ServiceError("Model not loaded")
        stops = normalize_stops(params.get("stop"))
        args = self._gen_args(params)
        try:
            # scrub_stream_delta holds back chars so a stop marker split
            # across chunk boundaries never leaks its prefix (execute()
            # scrubs the full text; streaming must match it byte-for-byte)
            acc = ""  # full raw accumulation
            emitted = 0  # chars of scrub(acc) already yielded
            n_new = None  # real token count, when the engine reports it
            timing = None  # engine timing breakdown off the done event
            n_seen = 0  # tokens streamed so far (the billable count on a
            # stop hit — the engine's own total never arrives then)
            record = current_timing()  # the gateway's timeline, if it opened one

            def content_line(text: str) -> str:
                if record is not None and not record.t_first_line:
                    record.t_first_line = time.perf_counter()
                return self.stream_line({"text": text})

            for ev in self.engine.generate_stream(**args):
                # the event is here (the wait for it is over): this turn of
                # the pump, its line's hand-over to the gateway included
                with pump_turn():
                    if ev.get("done"):  # flush the held-back tail
                        res = ev.get("result")
                        if res is not None:
                            n_new = res.new_tokens
                            timing = dict(res.timings)
                        tail = scrub_stop_words(acc, stops)
                        if tail[emitted:]:
                            yield content_line(tail[emitted:])
                        break
                    acc += ev.get("text", "")
                    n_seen += len(ev.get("tokens") or ([1] if ev.get("token") is not None else []))
                    delta, emitted, hit = scrub_stream_delta(acc, emitted, stops)
                    if delta:
                        yield content_line(delta)
                    if hit:
                        n_new = n_seen
                        break
            # the done line carries the node's REAL accounting so mesh
            # peers / the web gateway don't fall back to len/4 estimates
            done: dict[str, Any] = {"done": True}
            if n_new is not None:
                done["tokens"] = int(n_new)
                done["cost"] = self.price_per_token * int(n_new)
            if timing is not None:
                done["timing"] = timing
            yield self.stream_line(done)
        except Exception as e:  # match reference stream-error contract
            yield self.stream_line({"status": "error", "message": f"Stream error: {e}"})
