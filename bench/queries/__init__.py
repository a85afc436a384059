"""Parametrised TPC-H query templates, one per file (`q<N>.py`).

Each module copies the plan of `repro.tpch.queries.q<N>` with its
substitution parameters lifted out, as TPC-H's qgen draws them
(specification v3, clause 2.4), and exposes:

* `VALIDATION` — the specification's validation parameters;
* `domain()` — every parameter set qgen can draw, as a list of dicts;
* `plan(params)` — the plan the system under test runs.
"""
