"""Which form of model lacks which mechanism is ONE table
(``models.config.UNSUPPORTED``) behind ONE function (``refuse``): nothing
else in the package constructs the five errors, every row has a call site
and every call site a row, ``docs/serving.md`` shows the table, and
``refuse`` raises the first form of a row that a configuration is of.

``PYTHONPATH=. python tests/test_refusal_table.py`` prints that matrix."""

import ast
import pathlib
import re

import pytest

from senweaver_ide_tpu.models import config as mc

ROOT = pathlib.Path(__file__).resolve().parent.parent
PACKAGE = ROOT / "senweaver_ide_tpu"
FORMS = {
    mc.LatentCacheUnsupported: ("latent attention", "tiny-glm-moe-test"),
    mc.ResidualStreamUnsupported: ("residual streams", "tiny-xing-mhc-test"),
    mc.RecurrentStateUnsupported: ("recurrent state", "tiny-falcon-h1-test"),
    mc.ExpertShareUnsupported: ("expert share / shortcut",
                                "tiny-longcat-flash-test"),
    mc.LayerPatternUnsupported: ("layer pattern", "tiny-phi4flash-test"),
}
BEGIN, END = "<!-- refusals: begin -->", "<!-- refusals: end -->"


def _modules():
    for path in sorted(PACKAGE.rglob("*.py")):
        yield path.relative_to(ROOT).as_posix(), ast.parse(path.read_text())


def _called(node):
    f = node.func
    return f.id if isinstance(f, ast.Name) else getattr(f, "attr", None)


def test_only_the_table_s_module_constructs_the_errors():
    names = {cls.__name__ for cls in FORMS} | {"FormUnsupported"}
    made = {(path, _called(n)) for path, tree in _modules()
            for n in ast.walk(tree)
            if isinstance(n, ast.Call) and _called(n) in names}
    # not even there by name: ``refuse`` raises the row's class
    assert not made


def _asked_rows():
    """{row: [module:line]} over every ``refuse(config, <row>)`` of the
    package; a row given as a name is the loop variable of a literal
    tuple of ``(asked, "<row>")`` pairs in the same function."""
    rows = {}
    for path, tree in _modules():
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            looped = [pair.elts[-1].value for loop in ast.walk(fn)
                      if isinstance(loop, ast.For)
                      and isinstance(loop.iter, ast.Tuple)
                      for pair in loop.iter.elts
                      if isinstance(pair, ast.Tuple)
                      and isinstance(pair.elts[-1], ast.Constant)]
            for n in ast.walk(fn):
                if not (isinstance(n, ast.Call) and _called(n) == "refuse"):
                    continue
                row = n.args[1]
                named = ([row.value] if isinstance(row, ast.Constant)
                         else looped)
                assert named, f"{path}:{n.lineno}: refuse() names no row"
                for name in named:
                    rows.setdefault(name, []).append(f"{path}:{n.lineno}")
    return rows


def test_every_row_has_a_call_site_and_every_call_site_a_row():
    asked = _asked_rows()
    assert set(asked) == set(mc.UNSUPPORTED), (
        sorted(set(asked) ^ set(mc.UNSUPPORTED)))
    assert sum(map(len, asked.values())) >= len(mc.UNSUPPORTED)
    for row, cells in mc.UNSUPPORTED.items():
        assert cells and set(cells) <= set(FORMS), row


def matrix() -> str:
    """The table as the document shows it: mechanisms down, forms across,
    a cell the text the error calls the mechanism, the row's order of
    asking in brackets where a row has more than one form."""
    lines = ["| mechanism | " + " | ".join(
        f"{label} (`{cls.__name__}`)" for cls, (label, _) in FORMS.items())
        + " |", "|---" * (len(FORMS) + 1) + "|"]
    for row, cells in mc.UNSUPPORTED.items():
        order = {cls: i + 1 for i, cls in enumerate(cells)}
        lines.append(f"| `{row}` | " + " | ".join(
            (f"[{order[cls]}] " if len(cells) > 1 else "") + cells[cls]
            if cls in cells else "" for cls in FORMS) + " |")
    return "\n".join(lines)


def test_the_document_s_matrix_is_the_table():
    doc = (ROOT / "docs" / "serving.md").read_text()
    shown = re.search(re.escape(BEGIN) + r"\n(.*?)\n" + re.escape(END), doc,
                      re.S)
    assert shown, f"docs/serving.md has no {BEGIN} ... {END}"
    assert shown.group(1) == matrix(), (
        "docs/serving.md is not the table: write what "
        "`PYTHONPATH=. python tests/test_refusal_table.py` prints between the "
        "markers")


@pytest.mark.parametrize("row", sorted(mc.UNSUPPORTED))
def test_refuse_raises_the_first_form_of_the_row(row):
    cells = mc.UNSUPPORTED[row]
    mc.refuse(mc.get_config("tiny-test"), row)           # of no form
    mc.refuse(mc.get_config("tiny-moe-test"), row)
    for _, preset in FORMS.values():
        c = mc.get_config(preset)
        first = next((cls for cls in cells if cls.has(c)), None)
        if first is None:
            mc.refuse(c, row)
            continue
        with pytest.raises(mc.FormUnsupported) as err:
            mc.refuse(c, row)
        assert type(err.value) is first
        said = cells[first].format(c=c)
        assert err.value.mechanism == said
        assert str(err.value).startswith(said + " is not implemented for ")
        assert repr(c.name) in str(err.value)
        # a second configuration's form counts (a draft's)
        with pytest.raises(first):
            mc.refuse(mc.get_config("tiny-test"), row, also=c)


if __name__ == "__main__":
    print(matrix())
