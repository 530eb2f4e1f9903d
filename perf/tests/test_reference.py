"""The plain reference's column cleaners: a column's ``cleaner`` file is
applied to both records in ``Schema.score`` and in ``Corpus.matches``, a
column without one reads its values as they are, and a property fed
through two different cleaners is refused."""

import math

import numpy as np
import pytest

from reference import Corpus, Schema, parse_service

XML = """<DukeMicroService dataFolder="x">
  <Deduplication name="people">
    <duke>
      <schema>
        <threshold>0.8</threshold>
        <property><name>NAME</name><comparator>exact</comparator><low>0.1</low><high>0.95</high></property>
        <property><name>CITY</name><comparator>levenshtein</comparator><low>0.3</low><high>0.7</high></property>
      </schema>
      <data-source class="io.sesam.dukemicroservice.IncrementalDeduplicationDataSource">
        <param name="dataset-id" value="crm"/>
        <column name="name" property="NAME"{crm}/>
        <column name="city" property="CITY"/>
      </data-source>
      <data-source class="io.sesam.dukemicroservice.IncrementalDeduplicationDataSource">
        <param name="dataset-id" value="erp"/>
        <column name="name" property="NAME"{erp}/>
        <column name="city" property="CITY"/>
      </data-source>
    </duke>
  </Deduplication>
</DukeMicroService>
"""
CLEANED = ' cleaner="LowerCase"'


def schema(crm="", erp=""):
    return Schema(parse_service(XML.format(crm=crm, erp=erp)))


def bayes(*ps):
    prob = 0.5
    for p in ps:
        prob = prob * p / (prob * p + (1 - prob) * (1 - p))
    return prob


def test_parse_reads_the_cleaner():
    service = parse_service(XML.format(crm=CLEANED, erp=CLEANED))
    assert [(p["column"], p["cleaner"]) for p in service["properties"]] == \
        [("name", "lowercase"), ("city", None)]


def test_cleaner_applies_to_both_records():
    a = {"name": "  Ole   HANSEN ", "city": "oslo"}
    b = {"name": "ole hansen", "city": "Oslo"}
    c = {"name": "Ole Hansen", "city": "oslo"}
    cleaned = schema(CLEANED, CLEANED)
    # NAME equal once cleaned on either side; CITY has no cleaner, so
    # "Oslo" and "oslo" are one substitution apart: 1 - 1/4
    city = (0.7 - 0.5) * 0.75 ** 2 + 0.5
    for r1, r2 in ((a, b), (b, a), (a, c), (c, b)):
        assert cleaned.score(r1, r2) == pytest.approx(
            bayes(0.95, city if "Oslo" in (r1["city"], r2["city"]) else 0.7),
            rel=1e-15)
    d = {"name": "Ola Hansen", "city": "oslo"}
    query = {"name": "OLE hansen", "city": "oslo"}
    corpus = Corpus(cleaned, "abcd", [a, b, c, d])
    got = corpus.matches(query, 1e-9)
    assert got == pytest.approx({"a": bayes(0.95, 0.7),
                                 "b": bayes(0.95, city),
                                 "c": bayes(0.95, 0.7)}, rel=1e-15)
    assert set(corpus.matches(query, 0.0, np.float32)) == {"a", "b", "c"}
    # uncleaned, the query's name equals none of theirs
    assert Corpus(schema(), "abcd", [a, b, c, d]).matches(query, 1e-9) == {}


def test_a_value_empty_after_cleaning_skips_the_property():
    cleaned = schema(CLEANED, CLEANED)
    blank = {"name": "   ", "city": "oslo"}
    full = {"name": "ole hansen", "city": "oslo"}
    assert cleaned.score(blank, full) == pytest.approx(bayes(0.7),
                                                       rel=1e-15)
    corpus = Corpus(cleaned, ["blank"], [blank])
    assert corpus.columns[0].empty.tolist() == [True]
    # the bound counts only CITY: 0.7 alone cannot pass 0.8
    assert corpus.matches(full, 1e-9) == {}


def test_a_column_without_a_cleaner_reads_as_it_is():
    plain = schema()
    assert [p["cleaner"] for p in parse_service(XML.format(
        crm="", erp=""))["properties"]] == [None, None]
    a = {"name": "Ole Hansen", "city": "oslo"}
    b = {"name": "ole hansen", "city": "oslo"}
    assert plain.score(a, b) == pytest.approx(bayes(0.1, 0.7), rel=1e-15)
    assert plain.score(a, dict(a)) == pytest.approx(bayes(0.95, 0.7),
                                                    rel=1e-15)
    corpus = Corpus(plain, ["a", "b"], [a, b])
    assert set(corpus.matches(dict(a), 1e-9)) == {"a"}
    assert math.isclose(plain.logit_bound(), math.log(0.8 / 0.2))


@pytest.mark.parametrize("crm,erp", [
    (CLEANED, ""),
    ("", CLEANED),
    (CLEANED, ' cleaner="trim"'),
])
def test_one_property_through_two_cleaners_is_refused(crm, erp):
    with pytest.raises(ValueError, match="NAME"):
        parse_service(XML.format(crm=crm, erp=erp))


def test_one_property_from_two_columns_is_refused():
    xml = XML.format(crm="", erp="").replace(
        '<column name="name" property="NAME"/>\n        <column name="city"'
        ' property="CITY"/>\n      </data-source>\n    </duke>',
        '<column name="full_name" property="NAME"/>\n        <column '
        'name="city" property="CITY"/>\n      </data-source>\n    </duke>')
    assert "full_name" in xml
    with pytest.raises(ValueError, match="NAME"):
        parse_service(xml)
