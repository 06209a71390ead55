"""OpenAI-compatible API router over LLM deployments.

Reference parity: the ray.llm OpenAI router
(llm/_internal/serve/deployments/routers/router.py — /v1/models,
/v1/completions, /v1/chat/completions with SSE streaming) built as a
plain Serve deployment: the HTTP proxy maps a request path like
``/llm/v1/chat/completions`` to the ingress method
``v1_chat_completions`` (see serve/proxy.py path routing), and
``"stream": true`` in the body switches the proxy to the SSE path.

    app = build_openai_app([LLMConfig(model_id="m1"), ...])
    serve.run(app, name="llm", http_port=8000)
    # curl -X POST :8000/llm/v1/chat/completions -d '{"model": "m1", ...}'
"""
from __future__ import annotations

import time
from typing import Optional

from .serving import LLMConfig, build_llm_deployment


def apply_chat_template(messages: list[dict]) -> str:
    """Minimal generic chat template (the byte tokenizer has no special
    tokens; reference models bring their own via the tokenizer)."""
    parts = []
    for m in messages:
        role = m.get("role", "user")
        parts.append(f"<|{role}|>\n{m.get('content', '')}")
    parts.append("<|assistant|>\n")
    return "\n".join(parts)


class OpenAIRouter:
    """Ingress deployment: routes by the request's ``model`` field to the
    child LLM deployment handles bound in at build time."""

    def __init__(self, model_ids: list, *handles):
        self._handles = dict(zip(model_ids, handles))

    def _handle(self, body: dict):
        model = body.get("model", "")
        base = model.split(":", 1)[0] if model else ""
        if base in self._handles:
            return self._handles[base]
        if not base and len(self._handles) == 1:
            return next(iter(self._handles.values()))
        raise ValueError(
            f"unknown model {model!r}; serving: {list(self._handles)}")

    # path-routed methods (proxy: /app/v1/models -> v1_models) ---------- #

    def v1_models(self, _body: Optional[dict] = None) -> dict:
        return {"object": "list",
                "data": [{"id": mid, "object": "model",
                          "owned_by": "ray_tpu"}
                         for mid in self._handles]}

    def v1_completions(self, body: dict):
        body = dict(body or {})
        h = self._handle(body)
        if body.get("stream"):
            return self._sse(h, body)
        out = h.options(method_name="completions").remote(body).result(
            timeout_s=300)
        out.update(id=f"cmpl-{int(time.time() * 1000)}",
                   created=int(time.time()))
        return out

    def v1_chat_completions(self, body: dict):
        body = dict(body or {})
        body["prompt"] = apply_chat_template(body.get("messages", []))
        h = self._handle(body)
        if body.get("stream"):
            return self._sse(h, body, chat=True)
        out = h.options(method_name="completions").remote(body).result(
            timeout_s=300)
        text = out["choices"][0]["text"]
        return {
            "id": f"chatcmpl-{int(time.time() * 1000)}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": out["model"],
            "choices": [{
                "index": 0,
                "message": {"role": "assistant", "content": text},
                "finish_reason": out["choices"][0]["finish_reason"],
            }],
            "usage": out["usage"],
        }

    def _sse(self, h, body: dict, chat: bool = False):
        """Generator of SSE lines (the proxy streams these verbatim)."""
        import json
        gen = h.options(method_name="completions_stream",
                        stream=True).remote(body)
        for chunk in gen:
            if chat:
                delta = chunk["choices"][0]["text"]
                chunk = {
                    "object": "chat.completion.chunk",
                    "model": chunk["model"],
                    "choices": [{
                        "index": 0,
                        "delta": {"content": delta},
                        "finish_reason": chunk["choices"][0][
                            "finish_reason"],
                    }],
                }
            yield f"data: {json.dumps(chunk)}\n\n"
        yield "data: [DONE]\n\n"


def build_openai_app(configs: list[LLMConfig], params_refs=None):
    """[LLMConfig] -> Serve Application with the OpenAI router as ingress
    (reference: build_openai_app)."""
    from .. import serve
    params_refs = params_refs or [None] * len(configs)
    children = [build_llm_deployment(cfg, ref)
                for cfg, ref in zip(configs, params_refs)]
    # the router holds a request for as long as the model deployment it
    # forwards to does, and the front door's admission budget is the
    # INGRESS deployment's max_ongoing_requests: admit what the children
    # together admit (at the default of 16, 64 concurrent streams for a
    # 64-row engine were queued for 2 s and shed with 429)
    router = serve.deployment(
        OpenAIRouter, name="openai-router",
        max_ongoing_requests=sum(c.max_ongoing_requests for c in configs))
    return router.bind([c.model_id for c in configs], *children)
