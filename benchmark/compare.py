"""The comparison that decides ``correct``: after the window, a sample
of requests drawn from the seed goes through the daemon's own HTTP
routes (the routes the window drives) and each answer is held against
the plain reference's (``reference.py``) for the spans acked ``OK``;
then, with the daemon gone, its write-ahead log is held against the
acks (``walcheck.py``).

Every number compared is exact, so its limit is 0, but one:
``dependency_calls_off`` has the limit 32, set between the largest
reading of sound runs on full rings (1) and the smallest of the control
that drops one acked call (1,742); PERF.md gives the readings. The
daemon joins a child to a parent from an earlier call through a bounded
hash table (2 x capacity slots, 4 probes); at the load a full ring puts
on it an insert now and then finds all four probes taken and displaces
an older span, and a child whose parent went that way is not counted.

- ``answers_wrong``: sampled answers that differ (services, span names,
  the three query kinds, whole traces drawn from the newest spans the
  deployment holds whole: the longest and the last acked among them);
- ``dependency_calls_off``: sum over links of |calls - reference's|, a
  count over EVERY acked span that has a parent, so a lost or doubled
  ``Log`` call anywhere in the run shows (one call is some 1,750 links);
- ``routes_never_nonempty``: routes of the sample whose every answer was
  empty (an empty answer equal to an empty reference proves nothing);
- ``acked_calls_never_readable`` (counted by run.py): of the newest acked
  calls, those whose last span was still not readable a minute after the
  window closed;
- ``acked_spans_not_in_wal``, ``acks_before_durable`` (``walcheck.py``):
  durability, read off the disk and the fsync journal.
"""

from __future__ import annotations

import json

from reference import Reference, canonical_trace, hex_id

SELF_SERVICE = "zipkin-tpu"  # the daemon's self-trace service prefix
LIMITS = {"answers_wrong": 0, "dependency_calls_off": 32,
          "routes_never_nonempty": 0, "acked_calls_never_readable": 0,
          "acked_spans_not_in_wal": 0, "acks_before_durable": 0}


def compare(daemon, ref: Reference, rng, spec: dict, say) -> dict:
    wrong, compared = 0, 0
    nonempty = {}

    def judge(route: str, why, is_nonempty: bool) -> None:
        nonlocal wrong, compared
        compared += 1
        nonempty[route] = nonempty.get(route, 0) + bool(is_nonempty)
        if why:
            wrong += 1
            if wrong <= 3:  # the first few say enough
                say(f"WRONG {route}: {str(why)[:600]}")

    want = ref.services()
    got = [s for s in daemon.get_json("/api/services")
           if not s.startswith(SELF_SERVICE)]
    judge("services", None if got == want else f"want {want} got {got}", got)

    limit = spec.get("query_limit", 10)
    n_svc = min(spec.get("services", 16), len(want))
    for j in sorted(rng.choice(len(want), size=n_svc, replace=False)):
        svc = want[j]
        got = daemon.get_json("/api/spans", {"serviceName": svc})
        w = ref.span_names(svc)
        judge("spans", None if got == w else f"{svc}: want {w} got {got}", got)
        for route, extra in (
                ("query_service", {}),
                ("query_annotation",
                 {"annotationQuery": "some custom annotation"}),
                ("query_binary",
                 {"annotationQuery": "http.uri=/api/widgets"})):
            got = daemon.get_json(
                "/api/query", {"serviceName": svc, "limit": limit, **extra})
            judge(route, ref.check_query(svc, limit, got["traceIds"]),
                  got["traceIds"])

    n = ref.n_spans()
    picks = [ref.longest_trace(), ref.trace_id_of(n - 1)]
    picks += [ref.trace_id_of(int(i)) for i in rng.integers(
        ref.first_retained, n, size=spec.get("traces", 48))]
    for tid in dict.fromkeys(picks):
        w = ref.trace(tid)
        status, body = daemon.request("GET", f"/api/trace/{hex_id(tid)}")
        if status != 200:
            judge("trace", f"{hex_id(tid)}: HTTP {status}", False)
            continue
        got = canonical_trace(json.loads(body))
        judge("trace", None if got == w else
              f"{hex_id(tid)}: want {len(w)} spans {w[:1]} got {len(got)} "
              f"{got[:1]}", got)

    deps = daemon.get_json("/api/dependencies")
    got_links = {
        (l["parent"], l["child"]): l["durationMoments"]["count"]
        for l in deps["links"]
        if not (l["parent"].startswith(SELF_SERVICE)
                or l["child"].startswith(SELF_SERVICE))}
    want_links = ref.dependency_calls()
    off = sum(abs(got_links.get(k, 0) - want_links.get(k, 0))
              for k in set(got_links) | set(want_links))
    nonempty["dependencies"] = int(bool(got_links))
    compared += 1
    if off:
        bad = [k for k in set(got_links) | set(want_links)
               if got_links.get(k, 0) != want_links.get(k, 0)]
        say(f"WRONG dependencies: {len(bad)} links differ, e.g. "
            f"{[(k, got_links.get(k), want_links.get(k)) for k in bad[:3]]}")

    say(f"compared {compared} answers; nonempty {nonempty}")
    return {
        "answers_wrong": wrong,
        "dependency_calls_off": int(off),
        "routes_never_nonempty": sum(1 for v in nonempty.values() if not v),
    }
