"""Prompt tokens prefilled inside the window, per second of it.  The
client sees a prompt's work end at its first token; each request's prompt
tokens are spread evenly over the interval from its send to its first
token, and the part of that interval inside the window counts.  (Counting
whole prompts at their first token would move the number in steps of one
request, about a tenth of a window's work in a long-prompt cell.)"""


def read(run):
    t0, t1 = run.window
    total = 0.0
    for r in run.records:
        if not r["times"] or r["sent"] is None:
            continue
        a, b = r["sent"], r["times"][0]
        inside = min(b, t1) - max(a, t0)
        if b > a and inside > 0:
            total += len(r["prompt"]) * inside / (b - a)
    return total / run.seconds
