"""A feature script that reaches every leaf family of the unit fold.

Sum/avg/count (invertible scans), min/max and distinct_count (sparse
tables), topn_frequency and avg_cate_where, drawdown (a tree fold),
ew_avg (an exponentially weighted scan), over RANGE and ROWS frames,
with MAXSIZE and EXCLUDE CURRENT_ROW.  The kernel parity tests and the
chip smoke check fold it bit for bit against the staged engine.
"""

SQL = """
SELECT
  sum(price) OVER w3s AS s_price,
  avg(price) OVER w3s AS a_price,
  count(price) OVER w3s AS c_price,
  min(price) OVER w3s AS mn_price,
  max(price) OVER w3s AS mx_price,
  distinct_count(item) OVER w3s AS dc_item,
  topn_frequency(item, 3) OVER w3s AS topn_item,
  avg_cate_where(price, item, price > 1.0) OVER w3s AS acw,
  drawdown(price) OVER wr AS dd_price,
  ew_avg(price, 0.5) OVER wr AS ew_price,
  sum(price) OVER wx AS s_price_x,
  min(price) OVER wm AS mn_price_m
FROM actions
WINDOW w3s AS (PARTITION BY uid ORDER BY ts
               ROWS_RANGE BETWEEN 3s PRECEDING AND CURRENT ROW),
  wr AS (PARTITION BY uid ORDER BY ts
         ROWS BETWEEN 50 PRECEDING AND CURRENT ROW),
  wx AS (PARTITION BY uid ORDER BY ts
         ROWS_RANGE BETWEEN 5s PRECEDING AND CURRENT ROW
         MAXSIZE 7 EXCLUDE CURRENT_ROW),
  wm AS (PARTITION BY uid ORDER BY ts
         ROWS BETWEEN 10 PRECEDING AND CURRENT ROW MAXSIZE 4)
"""
