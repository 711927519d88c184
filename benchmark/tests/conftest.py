"""Since PR 43 the program hands out its picks (``submit(...,
keep_routing=True)`` -> ``Request.routed_experts``) and ``drivers/serve.py::
submit_checked`` asks for them. Two cases of ``test_serve_check.py`` were
written (PR 42) for a program that hands out NONE and still mean that:
``published_as_the_program_is`` (a mixture that does not renormalise is then
judged by a reference that routes by itself) and
``renormalised_and_no_picks_handed_out`` (refused). They get such a program:
``submit`` without the parameter, which is how ``submit_checked`` tells.
Every other test runs the program as it is."""
import pytest

NO_PICKS = ("test_the_serving_check[published_as_the_program_is]",
            "test_the_serving_check[renormalised_and_no_picks_handed_out]")


def submit_without_keep_routing(monkeypatch) -> None:
    """``ServingEngine.submit`` as it was before it took ``keep_routing``."""
    from deepspeed_tpu.serving.engine import ServingEngine
    plain = ServingEngine.submit

    def submit(self, prompt, max_new_tokens=32, **kw):
        return plain(self, prompt, max_new_tokens=max_new_tokens, **kw)
    monkeypatch.setattr(ServingEngine, "submit", submit)


@pytest.fixture(autouse=True)
def a_program_that_hands_out_no_picks(request, monkeypatch):
    if request.node.name in NO_PICKS and \
            request.node.module.__name__.endswith("test_serve_check"):
        submit_without_keep_routing(monkeypatch)
    yield
