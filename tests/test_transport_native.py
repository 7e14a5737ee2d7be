"""Anthropic-messages and Gemini native transports against a local
http.server emulating both wire formats (round-1 review: the
registry listed the styles but no client spoke them)."""

import json
import threading
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

from senweaver_ide_tpu.agents.llm import ChatMessage, RateLimitError
from senweaver_ide_tpu.context.rate_limiter import TPMRateLimiter
from senweaver_ide_tpu.transport import (AnthropicMessagesClient,
                                         GeminiClient, OpenAICompatClient,
                                         get_provider, make_client)

RECEIVED = {}


class _Handler(BaseHTTPRequestHandler):
    def log_message(self, *a):
        pass

    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        body = json.loads(self.rfile.read(n))
        RECEIVED[self.path] = {"body": body,
                               "headers": {k.lower(): v for k, v
                                           in self.headers.items()}}
        if self.path == "/v1/messages":
            if body.get("model") == "rate-limited":
                self.send_response(429)
                self.send_header("retry-after", "7")
                self.end_headers()
                self.wfile.write(b'{"error": "overloaded"}')
                return
            resp = {"model": body["model"],
                    "content": [{"type": "text", "text": "claude says hi"}],
                    "usage": {"input_tokens": 12, "output_tokens": 5}}
        elif ":generateContent" in self.path:
            resp = {"candidates": [{"content": {"parts":
                                                [{"text": "gemini "},
                                                 {"text": "says hi"}]}}],
                    "usageMetadata": {"promptTokenCount": 9,
                                      "candidatesTokenCount": 4},
                    "modelVersion": "gemini-test"}
        else:
            resp = {"error": "unknown path"}
        payload = json.dumps(resp).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.end_headers()
        self.wfile.write(payload)


@pytest.fixture(scope="module")
def server():
    httpd = HTTPServer(("127.0.0.1", 0), _Handler)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{httpd.server_port}"
    httpd.shutdown()


def test_anthropic_messages_shape(server):
    client = AnthropicMessagesClient(base_url=server, api_key="k-123",
                                     model="claude-test",
                                     rate_limiter=TPMRateLimiter())
    resp = client.chat([ChatMessage("system", "be brief"),
                        ChatMessage("user", "hello"),
                        ChatMessage("tool", "ok", tool_name="read_file")],
                       temperature=0.3, max_tokens=64)
    assert resp.text == "claude says hi"
    assert resp.usage.input_tokens == 12 and resp.usage.output_tokens == 5
    sent = RECEIVED["/v1/messages"]
    assert sent["headers"]["x-api-key"] == "k-123"
    assert "anthropic-version" in sent["headers"]
    body = sent["body"]
    assert body["system"] == "be brief"          # system is top-level
    assert body["max_tokens"] == 64              # required field
    assert body["messages"][0] == {"role": "user", "content": "hello"}
    assert body["messages"][1]["role"] == "user"
    assert "[read_file result]" in body["messages"][1]["content"]


def test_anthropic_rate_limit_maps(server):
    client = AnthropicMessagesClient(base_url=server, api_key="k",
                                     model="rate-limited",
                                     rate_limiter=TPMRateLimiter())
    with pytest.raises(RateLimitError) as e:
        client.chat([ChatMessage("user", "x")])
    assert e.value.retry_after_s == 7.0


def test_gemini_generate_content_shape(server):
    client = GeminiClient(base_url=server, api_key="g-key",
                          model="gemini-2.0-flash",
                          rate_limiter=TPMRateLimiter())
    resp = client.chat([ChatMessage("system", "terse"),
                        ChatMessage("user", "hi"),
                        ChatMessage("assistant", "prev")],
                       temperature=0.5, max_tokens=32)
    assert resp.text == "gemini says hi"
    assert resp.usage.input_tokens == 9
    assert resp.model == "gemini-test"
    key = "/v1beta/models/gemini-2.0-flash:generateContent"
    body = RECEIVED[key]["body"]
    assert RECEIVED[key]["headers"]["x-goog-api-key"] == "g-key"
    assert body["systemInstruction"]["parts"][0]["text"] == "terse"
    assert body["contents"][1]["role"] == "model"   # assistant → model
    assert body["generationConfig"] == {"temperature": 0.5,
                                        "maxOutputTokens": 32}


def test_make_client_dispatch(server):
    assert isinstance(make_client("anthropic", base_url=server,
                                  api_key="k"), AnthropicMessagesClient)
    assert isinstance(make_client("gemini", base_url=server, api_key="k"),
                      GeminiClient)
    assert isinstance(make_client("deepseek", api_key="k"),
                      OpenAICompatClient)
    with pytest.raises(ValueError, match="local"):
        make_client("local")


def test_registry_styles_are_live():
    """Every non-local endpoint style in the registry now has a client."""
    from senweaver_ide_tpu.transport.providers import PROVIDERS
    styles = {p.endpoint_style for p in PROVIDERS.values()}
    assert styles == {"local", "openai-compat", "anthropic", "gemini"}
    assert get_provider("gemini").endpoint_style == "gemini"
